"""The wire format of a dispatch: one packed buffer in, one result array out.

A launch costs the host by the piece, not by the byte (PERF.md section 6,
PRs 33, 35 and 43): every argument put and every result pulled is a round
trip of its own. So a dispatch crosses the host-device seam as ONE uint8
buffer and its answer comes back as ONE int32 array, on one chip
(`crypto/jax_backend.py`) as on a mesh (`parallel/mesh.py`, which lays the
same rows out shard-major and adds the psum verdict to each shard's tail).

A lane is one row of `ROW_BYTES` bytes: the 128 field bytes, then want_odd,
parity, has_t2, neg1, neg2 (int8: every flag is -1, 0 or 1), then valid and
live (0/1; `live` marks the rows a mesh's psum verdict counts, and a program
on one chip does not read it). Rows are widened value by value (`astype`) on
both sides of the seam, never reinterpreted across bytes, so the format has
no byte order. A result is `ok + 2 * needs_host` a row, then a tail: the
verdict checksum pair (count, weighted sum) over the pristine `ok`, and
whatever the program adds behind it.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "ROW_BYTES", "CHECKSUM_TAIL", "pad_rows", "pack_lanes", "unpack_lanes",
    "pack_result_traced", "split_result",
]

_FIELD_BYTES = 4 * 32
_N_FLAGS = 5
_VALID_COL = _FIELD_BYTES + _N_FLAGS
_LIVE_COL = _VALID_COL + 1
ROW_BYTES = _LIVE_COL + 1
CHECKSUM_TAIL = 2  # count, weighted sum

# Pad row values per kernel argument (mirrors `_pack_lanes`): fields 0,
# want_odd 0, parity -1 (don't-care), has_t2/neg1/neg2 0, valid False.
_PAD_VALUES = (0, 0, -1, 0, 0, 0, 0)
# A pad row's bytes after the fields: the flags and `valid` as above, not live.
_PAD_FLAGS = _PAD_VALUES[1:] + (0,)


def _lane_views(packed: np.ndarray):
    """Writable views over a packed buffer, in the kernel's argument order
    and then `live`: what is written through them is written to `packed`."""
    fields = packed[:, :_FIELD_BYTES]
    fields = np.lib.stride_tricks.as_strided(  # a reshape that cannot copy
        fields, (packed.shape[0], 4, 32), (packed.strides[0], 32, 1)
    )
    flags = packed.view(np.int8)
    return (
        (fields,)
        + tuple(flags[:, _FIELD_BYTES + i] for i in range(_N_FLAGS))
        + (packed[:, _VALID_COL], packed[:, _LIVE_COL])
    )


def pad_rows(rows: int) -> np.ndarray:
    """A fresh packed buffer of `rows` pad rows: valid False, not live."""
    packed = np.zeros((rows, ROW_BYTES), dtype=np.uint8)
    packed.view(np.int8)[:, _FIELD_BYTES:] = _PAD_FLAGS
    return packed


def pack_lanes(args, live, rows: int = None) -> np.ndarray:
    """The kernel's seven arguments and the `live` mask, row for row, as one
    fresh buffer of `ROW_BYTES` a lane: ONE pass out of buffers that may be
    read-only (the native lane prep's arena). `live` is a mask, or a count:
    that many first rows are live. With `rows` beyond what `args` hold, the
    rest are pad rows. The flags narrow to a byte each on the way; a row's
    seven bytes behind the fields go in as one block (a column at a time
    would walk the whole buffer seven times)."""
    k = int(args[0].shape[0])
    rows = k if rows is None else int(rows)
    packed = np.empty((rows, ROW_BYTES), dtype=np.uint8)
    packed[:k, :_FIELD_BYTES] = args[0].reshape(k, _FIELD_BYTES)
    block = np.empty((k, ROW_BYTES - _FIELD_BYTES), dtype=np.int8)
    for j, a in enumerate(args[1:]):
        block[:, j] = a
    if isinstance(live, (int, np.integer)):
        block[:, -1] = 0
        block[: int(live), -1] = 1
    else:
        block[:, -1] = live
    flags = packed.view(np.int8)[:, _FIELD_BYTES:]
    flags[:k] = block
    if rows > k:
        packed[k:] = pad_rows(rows - k)
    return packed


def unpack_lanes(packed: np.ndarray):
    """Host twin of a packed program's first ops, on a host buffer:
    `(fields, want_odd, parity, has_t2, neg1, neg2, valid, live)` as fresh
    arrays of the dtypes the kernel takes, bit for bit what `pack_lanes`
    was given."""
    flags = np.ascontiguousarray(
        packed[:, _FIELD_BYTES:_VALID_COL].view(np.int8).T
    ).astype(np.int32)
    fields = np.ascontiguousarray(packed[:, :_FIELD_BYTES]).reshape(-1, 4, 32)
    return (
        (fields,) + tuple(flags)
        + (packed[:, _VALID_COL] != 0, packed[:, _LIVE_COL] != 0)
    )


def _unpack_lanes_traced(packed):
    """`unpack_lanes` inside the traced program, on the rows it holds."""
    fields = packed[:, :_FIELD_BYTES].reshape(packed.shape[0], 4, 32)
    # uint8 -> int8 of the same width, then widened: parity's -1 is 0xff
    flags = jax.lax.bitcast_convert_type(
        packed[:, _FIELD_BYTES:_VALID_COL], jnp.int8
    ).astype(jnp.int32)
    return (
        (fields,) + tuple(flags[:, i] for i in range(_N_FLAGS))
        + (packed[:, _VALID_COL] != 0, packed[:, _LIVE_COL] != 0)
    )


def pack_result_traced(ok, needs, tail):
    """A program's one result inside the traced program: `ok + 2 * needs` a
    row, then `tail` (int32 scalars, the checksum pair first)."""
    rows = ok.astype(jnp.int32) + 2 * needs.astype(jnp.int32)
    return jnp.concatenate([rows, jnp.stack(tail)])


def split_result(raw: np.ndarray, parts: int, tail: int):
    """A settled result, on the host, of `parts` programs' outputs one
    after the other, as `(ok, needs_host, tails)`: the verdict buffer
    (bool), the deferral mask (int32, so that a row outside {0..3} fails
    the domain guard and is not masked away) and the `(parts, tail)` tails.
    Raises ValueError on a buffer that does not split `parts` ways."""
    if raw.ndim != 1 or raw.shape[0] % parts or raw.shape[0] // parts <= tail:
        raise ValueError(f"packed result {raw.shape} over {parts} shards")
    per_part = raw.reshape(parts, -1)
    rows = per_part[:, :-tail].reshape(-1)
    return (rows & 1) != 0, rows >> 1, per_part[:, -tail:]
