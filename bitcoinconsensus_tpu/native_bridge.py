"""ctypes bridge to the native host core (`native/libnat.so`).

SURVEY §7 prescribes a native host consensus core around the TPU crypto
backend; this module is its loader + typed surface. The library is built
on demand from the checked-in C++ sources (single `g++ -shared` call, a
few seconds, cached by mtime) so the repo never carries a binary.

Native components surfaced here:
- `prep_lanes`: batched verify-lane preparation (structural pubkey parse,
  lax-DER, high-S normalize, batched s^-1 mod n, BIP340 challenge hash,
  GLV split, byte packing) — the TpuSecpVerifier host_prep/pack phases in
  one C call.
- `verify_ecdsa` / `verify_schnorr` / `tweak_add_check`: host-exact
  scalar verifies (fast fallback path; the pure-Python
  `crypto/secp_host.py` stays the executable spec they are tested
  against).
- `sha256` / `sha256d` / `tagged_hash` utilities.

Set BITCOINCONSENSUS_TPU_NATIVE=0 to disable (pure-Python paths remain
fully functional and consensus-exact).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import warnings
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

__all__ = ["available", "lib", "why_absent", "prep_pack", "NativeSecp"]

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native"
)
_SO_PATH = os.path.join(_NATIVE_DIR, "libnat.so")
# Installed-package location: setup.py compiles the core into the wheel
# as bitcoinconsensus_tpu/_native/libnat.so (no source tree at runtime).
_PACKAGED_SO = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "_native", "libnat.so"
)
_SOURCES = ("nat.cpp", "secp.hpp", "sha256.hpp", "hash_extra.hpp", "interp.hpp", "eval.hpp", "block.hpp", "lru.hpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_why_absent: Optional[str] = None


def _build() -> Optional[str]:
    """(Re)build `libnat.so` from the checked-in sources when it is older
    than any of them. Returns None on success, else the reason — the
    compiler's stderr included."""
    srcs = [os.path.join(_NATIVE_DIR, s) for s in _SOURCES]
    missing = [s for s in srcs if not os.path.exists(s)]
    if missing:
        return "native sources missing: " + ", ".join(missing)
    if os.path.exists(_SO_PATH) and all(
        os.path.getmtime(_SO_PATH) >= os.path.getmtime(s) for s in srcs
    ):
        return None
    cmd = [
        os.environ.get("CXX", "g++"),
        "-O3",
        "-std=c++17",
        "-fPIC",
        "-shared",
        os.path.join(_NATIVE_DIR, "nat.cpp"),
        "-o",
        _SO_PATH,
    ]
    try:
        subprocess.run(
            cmd, check=True, capture_output=True, text=True, timeout=300
        )
    except subprocess.CalledProcessError as e:
        return f"`{' '.join(cmd)}` exited {e.returncode}:\n{e.stderr}"
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"`{' '.join(cmd)}` did not run: {e}"
    return None


def _absent(reason: str) -> None:
    """Record and announce why the native core is absent. `lib()` tries
    once per process, so this warns once; every caller then drops to the
    pure-Python engine (consensus-exact, roughly 10x slower host side)."""
    global _why_absent
    _why_absent = reason
    warnings.warn(
        "bitcoinconsensus_tpu: native host core unavailable, using the "
        f"pure-Python engine: {reason}",
        RuntimeWarning,
        stacklevel=3,
    )


def store_pool_bytes() -> int:
    """Bytes of retired index-mode check stores the native core keeps for
    the next session (native/interp.hpp `StorePool`: a released session's
    list of a megabyte or more is emptied and parked, not freed, so that
    the next connect does not fault its pages in again; 256 MB at most)."""
    return int(lib().nat_store_pool_bytes())


def sha256_transform() -> str:
    """Which SHA-256 compression the native core chose for this CPU at run
    time (native/sha256.hpp): `sha-ni` or `generic`."""
    return "sha-ni" if lib().nat_sha256_uses_sha_ni() else "generic"


def why_absent() -> Optional[str]:
    """Why `available()` is False (None while the core is loaded)."""
    if os.environ.get("BITCOINCONSENSUS_TPU_NATIVE", "") in ("0", "off"):
        return "disabled by BITCOINCONSENSUS_TPU_NATIVE"
    lib()
    return _why_absent


def lib() -> Optional[ctypes.CDLL]:
    """The loaded library, or None (unbuildable / disabled)."""
    global _lib, _tried
    if os.environ.get("BITCOINCONSENSUS_TPU_NATIVE", "") in ("0", "off"):
        return None
    with _lock:
        if _tried:
            return _lib
        _tried = True
        # Explicit override first (the sanitizer gate points this at
        # libnat_san.so); then source checkout: (re)build from the
        # checked-in sources; then wheel install: the .so setup.py
        # compiled into the package.
        override = os.environ.get("BITCOINCONSENSUS_NAT_SO", "")
        if override:
            so = override
        else:
            build_error = _build()
            if build_error is None:
                so = _SO_PATH
            elif os.path.exists(_PACKAGED_SO):
                so = _PACKAGED_SO
            else:
                return _absent(build_error)
        try:
            L = ctypes.CDLL(so)
        except OSError as e:
            return _absent(f"cannot load {so}: {e}")
        # ABI gate: a stale override/packaged .so with an older exported
        # surface (e.g. the pre-v4 recidx_data signature) must not load —
        # the typed prototypes below would mis-call it. Fall back to the
        # pure-Python paths instead.
        L.nat_version.restype = ctypes.c_int
        if L.nat_version() < 20:
            return _absent(f"{so} exports ABI v{L.nat_version()} (< 20)")
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        L.nat_version.restype = ctypes.c_int
        L.nat_prep_lanes.argtypes = [
            u8p, i64p, i32p, ctypes.c_int32,
            u8p, i32p, i32p, i32p, i32p, i32p, i32p,
        ]
        L.nat_prep_lanes.restype = None
        L.nat_verify_ecdsa.argtypes = [u8p, ctypes.c_int64, u8p, ctypes.c_int64, u8p]
        L.nat_verify_ecdsa.restype = ctypes.c_int
        L.nat_verify_schnorr.argtypes = [u8p, u8p, u8p]
        L.nat_verify_schnorr.restype = ctypes.c_int
        L.nat_tweak_add_check.argtypes = [u8p, ctypes.c_int32, u8p, u8p]
        L.nat_tweak_add_check.restype = ctypes.c_int
        L.nat_murmur3_32.argtypes = [ctypes.c_uint32, u8p, ctypes.c_int64]
        L.nat_murmur3_32.restype = ctypes.c_uint32
        L.nat_sha256.argtypes = [u8p, ctypes.c_int64, u8p]
        L.nat_sha256d.argtypes = [u8p, ctypes.c_int64, u8p]
        L.nat_tagged_hash.argtypes = [u8p, ctypes.c_int64, u8p, ctypes.c_int64, u8p]
        # interpreter surface
        vp = ctypes.c_void_p
        L.nat_session_new.restype = vp
        L.nat_session_free.argtypes = [vp]
        L.nat_session_add_known.argtypes = [
            vp, ctypes.c_int32, ctypes.c_int32,
            u8p, ctypes.c_int64, u8p, ctypes.c_int64, u8p, ctypes.c_int64,
            ctypes.c_int32,
        ]
        L.nat_session_records_count.argtypes = [vp]
        L.nat_session_records_count.restype = ctypes.c_int32
        L.nat_session_records_meta.argtypes = [vp, i32p, i32p, i64p]
        L.nat_session_records_data.argtypes = [vp, u8p]
        L.nat_session_records_bytes.argtypes = [vp]
        L.nat_session_records_bytes.restype = ctypes.c_int64
        L.nat_tx_parse.argtypes = [u8p, ctypes.c_int64]
        L.nat_tx_parse.restype = vp
        L.nat_tx_free.argtypes = [vp]
        L.nat_tx_ser_size.argtypes = [vp]
        L.nat_tx_ser_size.restype = ctypes.c_int64
        L.nat_tx_n_inputs.argtypes = [vp]
        L.nat_tx_n_inputs.restype = ctypes.c_int32
        L.nat_tx_wtxid.argtypes = [vp, u8p]
        L.nat_tx_set_spent_outputs.argtypes = [vp, i64p, u8p, i64p, ctypes.c_int32]
        L.nat_tx_precompute.argtypes = [vp]
        L.nat_verify_input.argtypes = [
            vp, vp, ctypes.c_int32, ctypes.c_int64, u8p, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32, i32p, i32p,
        ]
        L.nat_verify_input.restype = ctypes.c_int32
        # batched surfaces (one C call per phase, not per input/check)
        L.nat_verify_inputs.argtypes = [
            vp, ctypes.POINTER(ctypes.c_void_p), i32p, i64p, u8p, i64p, i32p,
            ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p, i64p,
        ]
        L.nat_session_spec_count.argtypes = [vp]
        L.nat_session_spec_count.restype = ctypes.c_int32
        L.nat_session_spec_meta.argtypes = [vp, i32p, i32p, i64p]
        L.nat_session_spec_bytes.argtypes = [vp]
        L.nat_session_spec_bytes.restype = ctypes.c_int64
        L.nat_session_spec_data.argtypes = [vp, u8p]
        L.nat_session_add_known_batch.argtypes = [
            vp, ctypes.c_int32, i32p, u8p, i64p, i32p,
        ]
        L.nat_digest_checks.argtypes = [
            u8p, ctypes.c_int64, ctypes.c_int32, i32p, u8p, i64p, u8p,
        ]
        L.nat_digest_streams.argtypes = [
            u8p, ctypes.c_int64, ctypes.c_int32, i64p, i64p, u8p, u8p,
        ]
        # index-mode surface (session-resident uniq protocol)
        L.nat_verify_inputs_idx.argtypes = [
            vp, ctypes.POINTER(ctypes.c_void_p), i32p, i64p, u8p, i64p, i32p,
            ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p, i64p,
        ]
        L.nat_session_uniq_count.argtypes = [vp]
        L.nat_session_uniq_count.restype = ctypes.c_int32
        L.nat_session_spec_pairings.argtypes = [vp]
        L.nat_session_spec_pairings.restype = ctypes.c_int64
        L.nat_store_pool_bytes.argtypes = []
        L.nat_store_pool_bytes.restype = ctypes.c_int64
        L.nat_session_call_walks.argtypes = [vp, i64p, ctypes.c_int64]
        L.nat_session_call_walks.restype = ctypes.c_int64
        L.nat_session_sighashes.argtypes = [vp, i64p]
        L.nat_session_sighashes.restype = None
        L.nat_session_sighash_work.argtypes = [vp, i64p]
        L.nat_session_sighash_work.restype = None
        L.nat_session_stages.argtypes = [vp, i64p]
        L.nat_session_stages.restype = None
        L.nat_sha256_uses_sha_ni.argtypes = []
        L.nat_sha256_uses_sha_ni.restype = ctypes.c_int32
        L.nat_session_lane_kinds.argtypes = [vp, i64p]
        L.nat_session_lane_kinds.restype = None
        L.nat_session_taproot_hashes.argtypes = [vp, i64p]
        L.nat_session_taproot_hashes.restype = None
        L.nat_session_recidx_data.argtypes = [vp, i32p, ctypes.c_int64]
        L.nat_session_recidx_data.restype = ctypes.c_int64
        L.nat_session_uniq_lanes.argtypes = [
            vp, i32p, ctypes.c_int32, ctypes.c_int32,
            u8p, i32p, i32p, i32p, i32p, i32p, i32p,
        ]
        L.nat_session_uniq_digests.argtypes = [
            vp, u8p, ctypes.c_int64, i32p, ctypes.c_int32, ctypes.c_int32,
            u8p,
        ]
        L.nat_prep_shards.argtypes = [ctypes.c_int32, ctypes.c_int32]
        L.nat_prep_shards.restype = ctypes.c_int32
        L.nat_session_publish_uniq.argtypes = [vp, i32p, ctypes.c_int32, i32p]
        L.nat_session_uniq_host_verify.argtypes = [vp, ctypes.c_int32]
        L.nat_session_uniq_host_verify.restype = ctypes.c_int32
        # block layer (native/block.hpp)
        L.nat_block_parse.argtypes = [u8p, ctypes.c_int64]
        L.nat_block_parse.restype = vp
        L.nat_block_free.argtypes = [vp]
        L.nat_block_n_tx.argtypes = [vp]
        L.nat_block_n_tx.restype = ctypes.c_int32
        L.nat_block_n_inputs.argtypes = [vp]
        L.nat_block_n_inputs.restype = ctypes.c_int32
        L.nat_block_tx.argtypes = [vp, ctypes.c_int32]
        L.nat_block_tx.restype = vp
        L.nat_block_tx_ptrs.argtypes = [
            vp, ctypes.POINTER(ctypes.c_void_p), ctypes.c_int32,
        ]
        L.nat_block_tx_ptrs.restype = ctypes.c_int32
        L.nat_block_nowit_sizes.argtypes = [vp, i64p]
        L.nat_block_txid.argtypes = [vp, ctypes.c_int32, u8p]
        L.nat_block_wtxid.argtypes = [vp, ctypes.c_int32, u8p]
        L.nat_block_check.argtypes = [vp, ctypes.c_int32, u8p, ctypes.c_int32]
        L.nat_block_check.restype = ctypes.c_int32
        L.nat_block_check_witness.argtypes = [vp]
        L.nat_block_check_witness.restype = ctypes.c_int32
        L.nat_block_accounting.argtypes = [
            vp, vp, ctypes.c_int64, ctypes.c_int32, u8p, ctypes.c_int64,
        ]
        L.nat_block_accounting.restype = ctypes.c_int32
        L.nat_block_acct_meta.argtypes = [vp, i64p, i64p, i64p, i64p]
        L.nat_block_acct_data.argtypes = [vp, i32p, i32p, i64p, i64p, u8p]
        L.nat_block_spent_digests.argtypes = [vp, u8p]
        L.nat_block_script_keys.argtypes = [vp, u8p]
        L.nat_block_script_keys.restype = ctypes.c_int64
        L.nat_block_coin_probes.argtypes = [vp, i64p]
        L.nat_block_coin_probes.restype = None
        L.nat_block_stages.argtypes = [vp, i64p]
        L.nat_block_stages.restype = None
        L.nat_view_new.restype = vp
        L.nat_view_free.argtypes = [vp]
        L.nat_view_clone.argtypes = [vp]
        L.nat_view_clone.restype = vp
        L.nat_view_len.argtypes = [vp]
        L.nat_view_len.restype = ctypes.c_int64
        L.nat_view_add_coins.argtypes = [
            vp, ctypes.c_int32, u8p, i32p, i64p, i32p, i32p, u8p, i64p,
        ]
        L.nat_view_get.argtypes = [vp, u8p, ctypes.c_int32, i64p, i32p, i32p, i64p]
        L.nat_view_get.restype = ctypes.c_int32
        L.nat_view_get_spk.argtypes = [vp, u8p, ctypes.c_int32, u8p]
        L.nat_view_spend.argtypes = [vp, u8p, ctypes.c_int32]
        L.nat_view_spend.restype = ctypes.c_int32
        L.nat_view_apply_block.argtypes = [vp, vp, ctypes.c_int64]
        L.nat_view_apply_block_undo.argtypes = [vp, vp, ctypes.c_int64]
        L.nat_view_apply_block_undo.restype = vp
        L.nat_view_undo_block.argtypes = [vp, vp, vp]
        L.nat_view_undo_block.restype = ctypes.c_int32
        L.nat_view_disconnect_block.argtypes = [
            vp, vp, vp, ctypes.c_int64, ctypes.c_int32, i64p]
        L.nat_view_disconnect_block.restype = ctypes.c_int32
        L.nat_undo_matches_block.argtypes = [vp, vp]
        L.nat_undo_matches_block.restype = ctypes.c_int32
        L.nat_undo_len.argtypes = [vp]
        L.nat_undo_len.restype = ctypes.c_int64
        L.nat_undo_free.argtypes = [vp]
        L.nat_view_digest.argtypes = [vp, u8p, ctypes.c_int32]
        # the success caches' key set (native/lru.hpp); keys go in as bytes
        keys = ctypes.c_char_p
        L.nat_lru_new.argtypes = [ctypes.c_int64]
        L.nat_lru_new.restype = vp
        L.nat_lru_free.argtypes = [vp]
        L.nat_lru_free.restype = None
        L.nat_lru_len.argtypes = [vp]
        L.nat_lru_len.restype = ctypes.c_int64
        L.nat_lru_probe.argtypes = [
            vp, keys, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, u8p, i64p]
        L.nat_lru_probe.restype = ctypes.c_int64
        L.nat_lru_add.argtypes = [
            vp, keys, ctypes.c_int64, i64p, ctypes.c_int64, i64p]
        L.nat_lru_add.restype = ctypes.c_int64
        L.nat_lru_discard.argtypes = [vp, keys, i32p]
        L.nat_lru_discard.restype = ctypes.c_int64
        L.nat_lru_keys.argtypes = [vp, u8p, ctypes.c_int64]
        L.nat_lru_keys.restype = ctypes.c_int64
        L.nat_lru_counters.argtypes = [vp, i64p]
        L.nat_lru_counters.restype = None
        _lib = L
        return _lib


def available() -> bool:
    return lib() is not None


def _u8p(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _i32p(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


_KIND_CODE = {"ecdsa": 0, "schnorr": 1, "tweak": 2}


def prep_pack(checks: Sequence, size: int):
    """Native _prep_lanes + _pack_lanes: returns the 7-tuple of padded
    arrays TpuSecpVerifier feeds the kernel, bit-identical to the Python
    packers (asserted by tests/test_native.py).

    `checks` are SigCheck-shaped (kind, data); `size >= len(checks)` is
    the padded batch size.
    """
    L = lib()
    assert L is not None
    n = len(checks)
    assert size >= n
    parts: List[bytes] = []
    offs = np.empty(3 * n + 1, dtype=np.int64)
    kinds = np.empty(n, dtype=np.int32)
    pos = 0
    for i, chk in enumerate(checks):
        d = chk.data
        if chk.kind == "tweak":
            # (tweaked32, parity, internal32, tweak32) ->
            # internal | tweak | tweaked, parity in the kind code
            p0, p1, p2 = d[2], d[3], d[0]
            kinds[i] = 2 | ((d[1] & 1) << 8)
        else:
            p0, p1, p2 = d[0], d[1], d[2]
            kinds[i] = _KIND_CODE[chk.kind]
        offs[3 * i] = pos
        offs[3 * i + 1] = pos + len(p0)
        offs[3 * i + 2] = pos + len(p0) + len(p1)
        pos += len(p0) + len(p1) + len(p2)
        parts.append(p0)
        parts.append(p1)
        parts.append(p2)
    offs[3 * n] = pos
    blob = np.frombuffer(b"".join(parts), dtype=np.uint8) if pos else np.zeros(
        1, dtype=np.uint8
    )

    fields = np.zeros((size, 4, 32), dtype=np.uint8)
    want_odd = np.zeros(size, dtype=np.int32)
    parity = np.full(size, -1, dtype=np.int32)
    has_t2 = np.zeros(size, dtype=np.int32)
    neg1 = np.zeros(size, dtype=np.int32)
    neg2 = np.zeros(size, dtype=np.int32)
    valid_i = np.zeros(size, dtype=np.int32)
    if n:
        L.nat_prep_lanes(
            _u8p(blob),
            offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            _i32p(kinds),
            n,
            _u8p(fields),
            _i32p(want_odd),
            _i32p(parity),
            _i32p(has_t2),
            _i32p(neg1),
            _i32p(neg2),
            _i32p(valid_i),
        )
    return fields, want_odd, parity, has_t2, neg1, neg2, valid_i != 0


_KIND_NAME = {0: "ecdsa", 1: "schnorr", 2: "tweak"}


def _pack_check_parts(checks: Sequence[Tuple[str, Tuple]]):
    """Flatten (kind, data) pairs into the (kinds, blob, offs) wire shape
    shared by add_known_batch / digest_checks: Record part order (ecdsa
    pubkey|sig|msg, schnorr pk32|sig64|msg, tweak q32|internal32|tweak32),
    tweak parity in kind bit 8."""
    n = len(checks)
    kinds = np.empty(n, dtype=np.int32)
    offs = np.empty(3 * n + 1, dtype=np.int64)
    parts: List[bytes] = []
    pos = 0
    for i, (kind, data) in enumerate(checks):
        if kind == "tweak":
            p0, p1, p2 = data[0], data[2], data[3]
            kinds[i] = 2 | ((int(data[1]) & 1) << 8)
        else:
            p0, p1, p2 = data
            kinds[i] = _KIND_CODE[kind]
        offs[3 * i] = pos
        offs[3 * i + 1] = pos + len(p0)
        offs[3 * i + 2] = pos + len(p0) + len(p1)
        pos += len(p0) + len(p1) + len(p2)
        parts.append(p0)
        parts.append(p1)
        parts.append(p2)
    offs[3 * n] = pos
    blob = (
        np.frombuffer(b"".join(parts), dtype=np.uint8)
        if pos
        else np.zeros(1, dtype=np.uint8)
    )
    return kinds, blob, offs


def prep_shards(n: int, n_threads: int) -> int:
    """Workers `NativeSession.uniq_lanes` / `uniq_digests` use for `n`
    entries given `n_threads`: 1 is the serial path (no thread made)."""
    return int(lib().nat_prep_shards(int(n), int(n_threads)))


def digest_checks(salt: bytes, checks: Sequence[Tuple[str, Tuple]]) -> List[bytes]:
    """Batched salted cache-key digests, byte-identical to
    models/sigcache.py `_key(_parts(...))` (asserted by tests)."""
    L = lib()
    assert L is not None
    n = len(checks)
    if n == 0:
        return []
    kinds, blob, offs = _pack_check_parts(checks)
    salt_a = np.frombuffer(salt, dtype=np.uint8) if salt else np.zeros(1, np.uint8)
    out = np.zeros(32 * n, dtype=np.uint8)
    L.nat_digest_checks(
        _u8p(salt_a), len(salt), n, _i32p(kinds), _u8p(blob),
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), _u8p(out),
    )
    raw = out.tobytes()
    return [raw[32 * i : 32 * i + 32] for i in range(n)]


def digest_streams(salt: bytes, items: Sequence[Tuple[bytes, ...]]) -> List[bytes]:
    """Batched salted digests over arbitrary part lists, byte-identical to
    models/sigcache.py `_SaltedLRU._key` (asserted by tests)."""
    L = lib()
    assert L is not None
    n = len(items)
    if n == 0:
        return []
    bounds = np.empty(n + 1, dtype=np.int64)
    bounds[0] = 0
    parts: List[bytes] = []
    for i, it in enumerate(items):
        parts.extend(it)
        bounds[i + 1] = len(parts)
    offs = np.empty(len(parts) + 1, dtype=np.int64)
    offs[0] = 0
    pos = 0
    for j, p in enumerate(parts):
        pos += len(p)
        offs[j + 1] = pos
    blob = (
        np.frombuffer(b"".join(parts), dtype=np.uint8)
        if pos
        else np.zeros(1, dtype=np.uint8)
    )
    salt_a = np.frombuffer(salt, dtype=np.uint8) if salt else np.zeros(1, np.uint8)
    out = np.zeros(32 * n, dtype=np.uint8)
    L.nat_digest_streams(
        _u8p(salt_a), len(salt), n,
        bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        _u8p(blob), _u8p(out),
    )
    raw = out.tobytes()
    return [raw[32 * i : 32 * i + 32] for i in range(n)]


class NativeTx:
    """Parsed-transaction handle (native/interp.hpp NTx). Holds the wire
    parse and the tx-wide precomputed hash aggregates on the C++ side."""

    __slots__ = ("_ptr", "n_inputs", "ser_size", "_wtxid")

    def __init__(self, raw: bytes):
        L = lib()
        assert L is not None
        arr = np.frombuffer(raw, dtype=np.uint8) if raw else np.zeros(1, np.uint8)
        ptr = L.nat_tx_parse(_u8p(arr), len(raw))
        if not ptr:
            raise ValueError("tx deserialize failed")
        self._ptr = ptr
        self.n_inputs = int(L.nat_tx_n_inputs(ptr))
        self.ser_size = int(L.nat_tx_ser_size(ptr))
        self._wtxid: Optional[bytes] = None

    @property
    def wtxid(self) -> bytes:
        if self._wtxid is None:
            out = np.zeros(32, dtype=np.uint8)
            lib().nat_tx_wtxid(self._ptr, _u8p(out))
            self._wtxid = out.tobytes()
        return self._wtxid

    def __del__(self):
        try:
            L = lib()
        except TypeError:  # interpreter shutdown tore down module globals
            return
        if L is not None and getattr(self, "_ptr", None):
            L.nat_tx_free(self._ptr)
            self._ptr = None

    def set_spent_outputs(self, spent: Sequence[Tuple[int, bytes]]) -> None:
        L = lib()
        amounts = np.asarray([a for a, _ in spent], dtype=np.int64)
        offs = np.zeros(len(spent) + 1, dtype=np.int64)
        for i, (_, spk) in enumerate(spent):
            offs[i + 1] = offs[i] + len(spk)
        blob_b = b"".join(spk for _, spk in spent)
        blob = np.frombuffer(blob_b, dtype=np.uint8) if blob_b else np.zeros(
            1, np.uint8
        )
        L.nat_tx_set_spent_outputs(
            self._ptr,
            amounts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            _u8p(blob),
            offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(spent),
        )

    def precompute(self) -> None:
        lib().nat_tx_precompute(self._ptr)


class NativeStages(NamedTuple):
    """One read of a handle's native stage clock (`NativeSession.stages()`,
    `NativeBlock.stages()`): `stages[(call, stage)]` = (seconds, times
    stamped), `fans[(call, stat)]` = seconds."""

    stages: Dict[Tuple[str, str], Tuple[float, int]]
    fans: Dict[Tuple[str, str], float]

    @staticmethod
    def table(keys, out) -> Dict[Tuple[str, str], Tuple[float, int]]:
        """The core's layout: a nanosecond count a key, then a stamp count a key."""
        n = len(keys)
        return {k: (out[i] / 1e9, int(out[n + i])) for i, k in enumerate(keys)}


class NativeSession:
    """Deferral session (the oracle + per-call check records). In index
    mode the deduped check list is the oracle: every check lies once in
    the session's arena and its verdict is a byte at its uniq index."""

    __slots__ = ("_h",)

    MODE_DEFER = 0
    MODE_EXACT = 1

    def __init__(self):
        L = lib()
        assert L is not None
        self._h = L.nat_session_new()

    @property
    def _ptr(self):
        """The native handle; a released session raises here instead of
        handing a freed pointer to C."""
        if not self._h:
            raise RuntimeError("NativeSession used after release()")
        return self._h

    def release(self) -> None:
        """Free the native session now. For the owner to call once its
        last reader is done (the batch and block drivers time it as the
        `release` phase); calling it again, or `__del__` after it, does
        nothing."""
        h, self._h = getattr(self, "_h", None), None
        if h:
            L = lib()
            if L is not None:
                L.nat_session_free(h)

    def __del__(self):
        try:
            self.release()
        except TypeError:  # interpreter shutdown tore down module globals
            pass

    def add_known(self, kind: str, data: Tuple, result: bool) -> None:
        """Publish one resolved check into the native oracle; key layout
        matches models/batch.py's `known` dict keys."""
        L = lib()
        if kind == "tweak":
            p0, parity, p1, p2 = data[0], int(data[1]), data[2], data[3]
            kcode = 2
        else:
            p0, p1, p2 = data
            parity = 0
            kcode = 0 if kind == "ecdsa" else 1
        a = np.frombuffer(p0, np.uint8) if p0 else np.zeros(1, np.uint8)
        b = np.frombuffer(p1, np.uint8) if p1 else np.zeros(1, np.uint8)
        c = np.frombuffer(p2, np.uint8) if p2 else np.zeros(1, np.uint8)
        L.nat_session_add_known(
            self._ptr, kcode, parity & 1,
            _u8p(a), len(p0), _u8p(b), len(p1), _u8p(c), len(p2),
            1 if result else 0,
        )

    def _drain(self, count_fn, meta_fn, bytes_fn, data_fn) -> List[Tuple[str, Tuple]]:
        n = int(count_fn(self._ptr))
        if n == 0:
            return []
        kinds = np.zeros(n, dtype=np.int32)
        parities = np.zeros(n, dtype=np.int32)
        lens = np.zeros(3 * n, dtype=np.int64)
        meta_fn(
            self._ptr, _i32p(kinds), _i32p(parities),
            lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        )
        total = int(bytes_fn(self._ptr))
        blob = np.zeros(max(total, 1), dtype=np.uint8)
        data_fn(self._ptr, _u8p(blob))
        raw = blob.tobytes()
        out: List[Tuple[str, Tuple]] = []
        pos = 0
        for i in range(n):
            l0, l1, l2 = int(lens[3 * i]), int(lens[3 * i + 1]), int(lens[3 * i + 2])
            p0 = raw[pos : pos + l0]
            p1 = raw[pos + l0 : pos + l0 + l1]
            p2 = raw[pos + l0 + l1 : pos + l0 + l1 + l2]
            pos += l0 + l1 + l2
            kind = _KIND_NAME[int(kinds[i])]
            if kind == "tweak":
                out.append((kind, (p0, int(parities[i]), p1, p2)))
            else:
                out.append((kind, (p0, p1, p2)))
        return out

    def take_records(self) -> List[Tuple[str, Tuple]]:
        """Drain the records of the last verify_input(s) call as
        (kind, data) tuples shaped exactly like SigCheck.data."""
        L = lib()
        return self._drain(
            L.nat_session_records_count, L.nat_session_records_meta,
            L.nat_session_records_bytes, L.nat_session_records_data,
        )

    def take_spec(self) -> List[Tuple[str, Tuple]]:
        """Drain the speculative CHECKMULTISIG pairings accumulated by
        deferring verifies (cleared on drain; the seen-set persists so a
        later re-interpretation never re-emits one)."""
        L = lib()
        return self._drain(
            L.nat_session_spec_count, L.nat_session_spec_meta,
            L.nat_session_spec_bytes, L.nat_session_spec_data,
        )

    def add_known_batch(
        self, entries: Sequence[Tuple[str, Tuple, bool]]
    ) -> None:
        """Publish many resolved checks in one C call."""
        L = lib()
        n = len(entries)
        if n == 0:
            return
        kinds, blob, offs = _pack_check_parts([(k, d) for k, d, _ in entries])
        results = np.fromiter(
            (1 if r else 0 for _, _, r in entries), dtype=np.int32, count=n
        )
        L.nat_session_add_known_batch(
            self._ptr, n, _i32p(kinds), _u8p(blob),
            offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), _i32p(results),
        )

    def verify_inputs(
        self,
        ntxs: Sequence[NativeTx],
        n_ins: Sequence[int],
        amounts: Sequence[int],
        script_pubkeys: Sequence[bytes],
        flags: Sequence[int],
        mode: int = MODE_DEFER,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, List[List[Tuple[str, Tuple]]]]:
        """Verify many inputs in ONE C call. Returns (ok, err, unknown)
        int32 arrays plus each input's recorded checks (SigCheck-shaped).
        Speculative records accumulate on the session; drain via take_spec."""
        L = lib()
        n = len(ntxs)
        assert n == len(n_ins) == len(amounts) == len(script_pubkeys) == len(flags)
        if n == 0:
            return (
                np.zeros(0, np.int32), np.zeros(0, np.int32),
                np.zeros(0, np.int32), [],
            )
        tx_ptrs = (ctypes.c_void_p * n)(*[t._ptr for t in ntxs])
        nin_a = np.asarray(n_ins, dtype=np.int32)
        amt_a = np.asarray(amounts, dtype=np.int64)
        flg_a = np.asarray(flags, dtype=np.int32)
        spk_offs = np.zeros(n + 1, dtype=np.int64)
        for i, spk in enumerate(script_pubkeys):
            spk_offs[i + 1] = spk_offs[i] + len(spk)
        blob_b = b"".join(script_pubkeys)
        blob = (
            np.frombuffer(blob_b, dtype=np.uint8)
            if blob_b
            else np.zeros(1, np.uint8)
        )
        ok = np.zeros(n, dtype=np.int32)
        err = np.zeros(n, dtype=np.int32)
        unk = np.zeros(n, dtype=np.int32)
        bounds = np.zeros(n + 1, dtype=np.int64)
        L.nat_verify_inputs(
            self._ptr, tx_ptrs, _i32p(nin_a),
            amt_a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), _u8p(blob),
            spk_offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            _i32p(flg_a), mode, n, _i32p(ok), _i32p(err), _i32p(unk),
            bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        )
        flat = self.take_records()
        per_input = [
            flat[int(bounds[i]) : int(bounds[i + 1])] for i in range(n)
        ]
        return ok, err, unk, per_input

    # --- Index-mode protocol (session-resident uniq checks) -----------
    # The fast batch driver: check bytes stay in C++; Python sees int32
    # indices into the session's deduped `uniq` list plus, on demand,
    # packed kernel lanes / salted digests computed in place.

    def verify_inputs_idx(
        self,
        ntxs: Sequence[NativeTx],
        n_ins: Sequence[int],
        amounts: Sequence[int],
        script_pubkeys: Sequence[bytes],
        flags: Sequence[int],
        n_threads: int = 1,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Deferring interpretation of many inputs in ONE C call,
        optionally sharded across `n_threads` worker threads (the
        checkqueue.h:29-163 fan-out axis; the GIL is released for the
        duration). Returns (ok, err, unknown, rec_idx, rec_bounds):
        input i's oracle misses are uniq indices
        rec_idx[rec_bounds[i]:rec_bounds[i+1]]."""
        L = lib()
        n = len(ntxs)
        if n == 0:
            z32 = np.zeros(0, np.int32)
            return z32, z32, z32, z32, np.zeros(1, np.int64)
        tx_ptrs = (ctypes.c_void_p * n)(*[t._ptr for t in ntxs])
        nin_a = np.asarray(n_ins, dtype=np.int32)
        amt_a = np.asarray(amounts, dtype=np.int64)
        flg_a = np.asarray(flags, dtype=np.int32)
        spk_offs = np.zeros(n + 1, dtype=np.int64)
        for i, spk in enumerate(script_pubkeys):
            spk_offs[i + 1] = spk_offs[i] + len(spk)
        blob_b = b"".join(script_pubkeys)
        blob = (
            np.frombuffer(blob_b, dtype=np.uint8)
            if blob_b
            else np.zeros(1, np.uint8)
        )
        return self._run_idx(tx_ptrs, nin_a, amt_a, blob, spk_offs, flg_a,
                             n, n_threads)

    def verify_inputs_idx_raw(
        self,
        tx_ptrs: np.ndarray,
        n_ins: np.ndarray,
        amounts: np.ndarray,
        spk_blob: np.ndarray,
        spk_offs: np.ndarray,
        flags: np.ndarray,
        n_threads: int = 1,
    ):
        """Array-native variant of verify_inputs_idx: the scriptPubKeys
        arrive as one (blob, offs) pair — zero copies when the caller
        already holds the block accounting's arrays (models/validate.py
        _connect_block_native). `tx_ptrs` is the per-input column of raw
        NTx pointers (a gather from `NativeBlock.tx_ptrs()`)."""
        n = len(tx_ptrs)
        if n == 0:
            z32 = np.zeros(0, np.int32)
            return z32, z32, z32, z32, np.zeros(1, np.int64)
        ptr_a = np.ascontiguousarray(tx_ptrs, dtype=np.uintp)
        ptrs = ptr_a.ctypes.data_as(ctypes.POINTER(ctypes.c_void_p))
        nin_a = np.ascontiguousarray(n_ins, dtype=np.int32)
        amt_a = np.ascontiguousarray(amounts, dtype=np.int64)
        flg_a = np.ascontiguousarray(flags, dtype=np.int32)
        offs_a = np.ascontiguousarray(spk_offs, dtype=np.int64)
        blob = (
            np.ascontiguousarray(spk_blob, dtype=np.uint8)
            if len(spk_blob)
            else np.zeros(1, np.uint8)
        )
        return self._run_idx(ptrs, nin_a, amt_a, blob, offs_a, flg_a, n,
                             n_threads)

    def _run_idx(self, tx_ptrs, nin_a, amt_a, blob, spk_offs, flg_a, n,
                 n_threads):
        L = lib()
        ok = np.zeros(n, dtype=np.int32)
        err = np.zeros(n, dtype=np.int32)
        unk = np.zeros(n, dtype=np.int32)
        bounds = np.zeros(n + 1, dtype=np.int64)
        L.nat_verify_inputs_idx(
            self._ptr, tx_ptrs, _i32p(nin_a),
            amt_a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), _u8p(blob),
            spk_offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            _i32p(flg_a), n, int(n_threads), _i32p(ok), _i32p(err),
            _i32p(unk),
            bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        )
        n_idx = int(bounds[n])
        rec_idx = np.zeros(max(n_idx, 1), dtype=np.int32)
        if n_idx:
            got = int(L.nat_session_recidx_data(self._ptr, _i32p(rec_idx), n_idx))
            if got != n_idx:  # concurrent session mutation or ABI skew
                raise RuntimeError(f"recidx_data short copy: {got} != {n_idx}")
        return ok, err, unk, rec_idx[:n_idx], bounds

    def uniq_count(self) -> int:
        return int(lib().nat_session_uniq_count(self._ptr))

    def spec_pairings(self) -> int:
        """CHECKMULTISIG pairings pre-recorded into this session's uniq
        list so far (index mode; entries a speculation made, not a key
        walk): monotone over the session's life."""
        return int(lib().nat_session_spec_pairings(self._ptr))

    def call_walks(self, n: int) -> np.ndarray:
        """(signature, key) pairings CHECKMULTISIG's cursor walk tried in
        each interpretation of this session's newest verify call, by the
        input's position in that call (`n`: how many it held; one after
        `verify_input`): what Core's own walk verifies for that
        interpretation's verdict. The next call overwrites it, so a
        fixpoint reads it a round and adds an input's count when it accepts
        the verdict."""
        out = np.zeros(max(n, 1), dtype=np.int64)
        got = lib().nat_session_call_walks(
            self._ptr, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n)
        return out[:int(got)]

    def sighashes(self) -> Tuple[int, int]:
        """ECDSA message digests this session's interpretations hashed, and
        reads of one a CHECKMULTISIG had already made for the same
        signature (or the same hash-type byte): (computed, reused),
        monotone over the session's life."""
        out = (ctypes.c_int64 * 2)()
        lib().nat_session_sighashes(self._ptr, out)
        return int(out[0]), int(out[1])

    SIGHASH_KINDS = ("legacy", "bip143")

    def sighash_work(self) -> Dict[str, Tuple[int, float]]:
        """What the digests `sighashes()` counts as computed cost so far, by
        kind (`SIGHASH_KINDS`): (bytes of the preimages hashed, seconds of
        thread time from a digest's first byte to its double hash, a legacy
        template's build included, which the core counts in nanoseconds). A
        legacy preimage is the whole transaction with the other inputs'
        scripts blanked, and the bytes are those fed to SHA-256: a digest
        that resumed from its template's grid (`sighash_templates`) does not
        count what that state had absorbed; a BIP 143 one is the 156 bytes
        and the script code. Monotone over the session's life."""
        out = self._sighash_counts()
        return {k: (out[i], out[2 + i] / 1e9)
                for i, k in enumerate(self.SIGHASH_KINDS)}

    def _sighash_counts(self) -> List[int]:
        out = (ctypes.c_int64 * 7)()
        lib().nat_session_sighash_work(self._ptr, out)
        return [int(v) for v in out]

    TEMPLATE_EVENTS = ("built", "served", "resumed")

    def sighash_templates(self) -> Dict[str, int]:
        """The blanked templates those legacy digests were hashed from
        (`TEMPLATE_EVENTS`): `built`, the times a transaction laid its
        template down (once, on the first legacy digest anyone asks of it,
        whatever the thread count; once more where SIGHASH_NONE or
        SIGHASH_SINGLE is also signed), `served`, the digests hashed
        from one (every legacy digest without SIGHASH_ANYONECANPAY but the
        SIGHASH_SINGLE one that is the number one), and `resumed`, those of
        the served that started from a SHA-256 state the template keeps
        every 4,096 bytes of the stream its digests share (none in a
        transaction under ~100 inputs: its stream has no such point).
        Monotone over the session's life."""
        return dict(zip(self.TEMPLATE_EVENTS, self._sighash_counts()[4:]))

    # The native stage clock (native/interp.hpp): the session's three calls
    # that fan out, each call's serial stages in the order the core keeps
    # them, and what a fan-out says of itself.
    STAGES = (("interpret", "setup"), ("interpret", "workers"), ("interpret", "merge"),
              ("lanes", "order"), ("lanes", "shards"), ("digests", "shards"))
    FAN_CALLS = ("interpret", "lanes", "digests")
    FAN_STATS = ("wall", "held", "sum", "max", "start_lag", "tail")

    def stages(self) -> "NativeStages":
        """What this session's native calls spent beneath the ctypes
        boundary so far, on the clock of `time.perf_counter` (one read of
        the core's table). `.stages[(call, stage)]` is (seconds, times
        stamped) for the serial stages that tile a call: `interpret`
        (`verify_inputs_idx`) is `setup` (the scratch sessions and the store
        pool), `workers` (around the fan-out) and `merge` (the scratches'
        counters summed and the serial merge in index order, to the return);
        `lanes` (`uniq_lanes`) is `order` (the serial `lanes_order` loop) and
        `shards` (around the fan-out); `digests` (`uniq_digests`) is
        `shards`. `.fans[(call, stat)]` is seconds, what the call's fan-outs
        said of themselves (`FAN_STATS`): `wall` entry to joined, `held` the
        width times that, `sum` and `max` the workers' busy seconds summed
        and the slowest's, `start_lag` entry to the latest worker's first
        instruction, `tail` the last-ended worker's last instruction to
        joined. `max` times the width over `sum` says how level the calls
        ended (1.0: every worker busy as long as the slowest); a call on the
        caller's thread is one worker, `start_lag` 0 and `sum` = `max` =
        `wall`. Monotone over the session's life."""
        n, f = len(self.STAGES), len(self.FAN_STATS)
        out = (ctypes.c_int64 * (2 * n + len(self.FAN_CALLS) * f))()
        lib().nat_session_stages(self._ptr, out)
        return NativeStages(
            NativeStages.table(self.STAGES, out),
            {(call, stat): out[2 * n + c * f + j] / 1e9
             for c, call in enumerate(self.FAN_CALLS)
             for j, stat in enumerate(self.FAN_STATS)},
        )

    LANE_KINDS = ("ecdsa", "schnorr", "tweak")
    TAPROOT_HASHES = ("sighash", "leaf", "branch", "tweak")

    def lane_kinds(self) -> Dict[str, int]:
        """Lanes `uniq_lanes` has prepped out of this session so far, by
        the kind of check (`LANE_KINDS`): what its fixpoint sent to the
        device. Monotone over the session's life."""
        out = (ctypes.c_int64 * 3)()
        lib().nat_session_lane_kinds(self._ptr, out)
        return dict(zip(self.LANE_KINDS, map(int, out)))

    def taproot_hashes(self) -> Dict[str, int]:
        """Taproot hashes this session's interpretations made so far
        (`TAPROOT_HASHES`): BIP 341 message digests, key path and tapscript
        alike, and the commitment's TapLeaf, TapBranch and TapTweak hashes.
        Monotone over the session's life."""
        out = (ctypes.c_int64 * 4)()
        lib().nat_session_taproot_hashes(self._ptr, out)
        return dict(zip(self.TAPROOT_HASHES, map(int, out)))

    def uniq_lanes(self, idxs: np.ndarray, size: int, n_threads: int = 1):
        """Packed kernel lanes for the uniq entries `idxs`, padded to
        `size` — the session-resident twin of prep_pack. The native call
        shards them over `prep_shards(len(idxs), n_threads)` workers; the
        arrays are the same byte for byte at any width."""
        L = lib()
        n = len(idxs)
        assert size >= n
        idx_a = np.ascontiguousarray(idxs, dtype=np.int32)
        fields = np.zeros((size, 4, 32), dtype=np.uint8)
        want_odd = np.zeros(size, dtype=np.int32)
        parity = np.full(size, -1, dtype=np.int32)
        has_t2 = np.zeros(size, dtype=np.int32)
        neg1 = np.zeros(size, dtype=np.int32)
        neg2 = np.zeros(size, dtype=np.int32)
        valid_i = np.zeros(size, dtype=np.int32)
        if n:
            L.nat_session_uniq_lanes(
                self._ptr, _i32p(idx_a), n, int(n_threads), _u8p(fields),
                _i32p(want_odd), _i32p(parity), _i32p(has_t2), _i32p(neg1),
                _i32p(neg2), _i32p(valid_i),
            )
        return fields, want_odd, parity, has_t2, neg1, neg2, valid_i != 0

    def uniq_digests(self, salt: bytes, idxs: np.ndarray,
                     n_threads: int = 1) -> np.ndarray:
        """(n, 32) uint8 salted cache-key digests for uniq entries
        `idxs`, computed in place (no check bytes cross the bridge);
        sharded by count as `uniq_lanes` is."""
        L = lib()
        n = len(idxs)
        out = np.zeros((max(n, 1), 32), dtype=np.uint8)
        if n:
            idx_a = np.ascontiguousarray(idxs, dtype=np.int32)
            salt_a = (
                np.frombuffer(salt, dtype=np.uint8)
                if salt
                else np.zeros(1, np.uint8)
            )
            L.nat_session_uniq_digests(
                self._ptr, _u8p(salt_a), len(salt), _i32p(idx_a), n,
                int(n_threads), _u8p(out),
            )
        return out[:n]

    def publish_uniq(self, idxs: np.ndarray, results: np.ndarray) -> None:
        """Publish verdicts for uniq entries `idxs` into the native
        oracle (a verdict byte an entry) without round-tripping check
        bytes."""
        L = lib()
        n = len(idxs)
        if n == 0:
            return
        idx_a = np.ascontiguousarray(idxs, dtype=np.int32)
        res_a = np.ascontiguousarray(results, dtype=np.int32)
        L.nat_session_publish_uniq(self._ptr, _i32p(idx_a), n, _i32p(res_a))

    def uniq_host_verify(self, idx: int) -> bool:
        """Exact native verdict for one uniq entry (exceptional-lane
        fixup)."""
        return bool(lib().nat_session_uniq_host_verify(self._ptr, int(idx)))

    def verify_input(
        self,
        ntx: NativeTx,
        n_in: int,
        amount: int,
        script_pubkey: bytes,
        flags: int,
        mode: int = MODE_DEFER,
    ) -> Tuple[bool, int, int]:
        """(ok, script_error_code, unknown_count); records via take_records."""
        L = lib()
        spk = (
            np.frombuffer(script_pubkey, np.uint8)
            if script_pubkey
            else np.zeros(1, np.uint8)
        )
        serr = np.zeros(1, dtype=np.int32)
        unk = np.zeros(1, dtype=np.int32)
        ok = L.nat_verify_input(
            self._ptr, ntx._ptr, n_in, amount, _u8p(spk), len(script_pubkey),
            flags, mode, _i32p(serr), _i32p(unk),
        )
        return bool(ok), int(serr[0]), int(unk[0])


# BlkReason code -> reference reject-reason string (native/block.hpp
# BlkReason order is part of the ABI; index = code).
BLOCK_REASONS = (
    None,
    "high-hash",
    "bad-txnmrklroot",
    "bad-txns-duplicate",
    "bad-blk-length",
    "bad-cb-missing",
    "bad-cb-multiple",
    "bad-txns-vin-empty",
    "bad-txns-vout-empty",
    "bad-txns-oversize",
    "bad-txns-vout-negative",
    "bad-txns-vout-toolarge",
    "bad-txns-txouttotal-toolarge",
    "bad-txns-inputs-duplicate",
    "bad-cb-length",
    "bad-txns-prevout-null",
    "bad-blk-sigops",
    "bad-witness-nonce-size",
    "bad-witness-merkle-match",
    "unexpected-witness",
    "bad-txns-BIP30",
    "bad-txns-inputs-missingorspent",
    "bad-txns-premature-spend-of-coinbase",
    "bad-txns-inputvalues-outofrange",
    "bad-txns-in-belowout",
    "bad-txns-fee-outofrange",
    "bad-cb-amount",
    "block-deserialize-failed",
)


class NativeBlockTx:
    """Borrowed tx handle inside a NativeBlock (NOT freed on __del__ —
    the block owns it; the `_blk` backref keeps the owner alive for the
    handle's lifetime). Duck-compatible with NativeTx where the batch
    drivers need it (._ptr, .n_inputs, .ser_size, .wtxid)."""

    __slots__ = ("_ptr", "_blk", "n_inputs", "ser_size", "_wtxid", "_index",
                 "__weakref__")

    def __init__(self, blk: "NativeBlock", index: int, ptr):
        L = lib()
        self._blk = blk  # keeps the owning block alive
        self._index = index
        self._ptr = ptr
        self.n_inputs = int(L.nat_tx_n_inputs(ptr))
        self.ser_size = int(L.nat_tx_ser_size(ptr))
        self._wtxid: Optional[bytes] = None

    @property
    def wtxid(self) -> bytes:
        if self._wtxid is None:
            out = np.zeros(32, dtype=np.uint8)
            lib().nat_block_wtxid(self._blk._ptr, self._index, _u8p(out))
            self._wtxid = out.tobytes()
        return self._wtxid


class NativeBlock:
    """Parsed-block handle (native/block.hpp NBlock): header, txs, txids,
    and (after `accounting`) the per-input script-phase data."""

    __slots__ = ("_ptr", "n_tx", "n_inputs", "_txs")

    def __init__(self, raw: bytes):
        L = lib()
        assert L is not None
        arr = np.frombuffer(raw, dtype=np.uint8) if raw else np.zeros(1, np.uint8)
        ptr = L.nat_block_parse(_u8p(arr), len(raw))
        if not ptr:
            raise ValueError("block deserialize failed")
        self._ptr = ptr
        self.n_tx = int(L.nat_block_n_tx(ptr))
        self.n_inputs = int(L.nat_block_n_inputs(ptr))
        # weak values: a NativeBlockTx strongly refs its block, so a
        # strong cache here would form a cycle only cycle-GC could free —
        # and the block pipeline runs under gc_paused(). Weak entries die
        # with their last external ref; recreation is two C calls.
        import weakref

        self._txs = weakref.WeakValueDictionary()

    def __del__(self):
        try:
            L = lib()
        except TypeError:  # interpreter shutdown tore down module globals
            return
        if L is not None and getattr(self, "_ptr", None):
            L.nat_block_free(self._ptr)
            self._ptr = None

    def __deepcopy__(self, memo):
        # A deep copy would duplicate the raw C++ pointer and double-free;
        # the handle is a drop-on-copy cache (models/validate.py re-parses).
        return None

    def __reduce__(self):
        raise TypeError("NativeBlock handles are not picklable")

    def tx(self, i: int) -> NativeBlockTx:
        t = self._txs.get(i)
        if t is None:
            ptr = lib().nat_block_tx(self._ptr, i)
            assert ptr, i
            t = self._txs[i] = NativeBlockTx(self, i, ptr)
        return t

    def tx_ptrs(self) -> np.ndarray:
        """(n_tx,) uintp raw NTx pointers, one C call for the whole block.
        The block owns the txs, so the table is good for as long as the
        caller holds this handle; index it with `tx_index` for the
        per-input column `verify_inputs_idx_raw` takes."""
        out = np.zeros(max(self.n_tx, 1), dtype=np.uintp)
        got = lib().nat_block_tx_ptrs(
            self._ptr, out.ctypes.data_as(ctypes.POINTER(ctypes.c_void_p)),
            self.n_tx,
        )
        assert got == self.n_tx, (got, self.n_tx)
        return out[: self.n_tx]

    def txid(self, i: int) -> bytes:
        out = np.zeros(32, dtype=np.uint8)
        lib().nat_block_txid(self._ptr, i, _u8p(out))
        return out.tobytes()

    def wtxid(self, i: int) -> bytes:
        out = np.zeros(32, dtype=np.uint8)
        lib().nat_block_wtxid(self._ptr, i, _u8p(out))
        return out.tobytes()

    def nowit_sizes(self) -> np.ndarray:
        """(n_tx,) serialized size of every tx without its witness."""
        out = np.zeros(max(self.n_tx, 1), dtype=np.int64)
        lib().nat_block_nowit_sizes(
            self._ptr, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        return out[: self.n_tx]

    def check(self, check_pow: bool, pow_limit: int, check_merkle: bool = True
              ) -> Optional[str]:
        """Context-free CheckBlock; returns a reject reason or None."""
        limit = np.frombuffer(pow_limit.to_bytes(32, "big"), dtype=np.uint8)
        code = lib().nat_block_check(
            self._ptr, 1 if check_pow else 0, _u8p(limit),
            1 if check_merkle else 0,
        )
        return BLOCK_REASONS[code]

    def check_witness_commitment(self) -> Optional[str]:
        return BLOCK_REASONS[lib().nat_block_check_witness(self._ptr)]

    def accounting(self, view: "NativeCoinsView", height: int, flags: int,
                   salt: Optional[bytes] = None):
        """ConnectBlock accounting (BIP30, existence/maturity/values, fees,
        sigop budget) + per-input script-phase data + per-tx hash
        precompute. Returns (reason|None, fees, sigop_cost, tx_index,
        n_in, amounts, spk_offs, spk_blob) — arrays one entry per
        non-coinbase input, in block order. With `salt` (a script
        execution cache's) it also makes every input's cache key, for
        `script_keys()` to hand out."""
        L = lib()
        if salt is None:
            salt_p, salt_len = None, 0
        else:
            salt_a = (np.frombuffer(salt, dtype=np.uint8) if salt
                      else np.zeros(1, np.uint8))
            salt_p, salt_len = _u8p(salt_a), len(salt)
        code = L.nat_block_accounting(
            self._ptr, view._ptr, height, flags, salt_p, salt_len)
        fees = np.zeros(1, np.int64)
        sigops = np.zeros(1, np.int64)
        n_in_total = np.zeros(1, np.int64)
        spk_bytes = np.zeros(1, np.int64)
        i64c = ctypes.POINTER(ctypes.c_int64)
        L.nat_block_acct_meta(
            self._ptr, fees.ctypes.data_as(i64c), sigops.ctypes.data_as(i64c),
            n_in_total.ctypes.data_as(i64c), spk_bytes.ctypes.data_as(i64c),
        )
        if code != 0:
            return (BLOCK_REASONS[code], int(fees[0]), int(sigops[0])) + (None,) * 5
        n = int(n_in_total[0])
        tx_index = np.zeros(max(n, 1), np.int32)
        n_in = np.zeros(max(n, 1), np.int32)
        amounts = np.zeros(max(n, 1), np.int64)
        spk_offs = np.zeros(n + 1, np.int64)
        spk_blob = np.zeros(max(int(spk_bytes[0]), 1), np.uint8)
        L.nat_block_acct_data(
            self._ptr, _i32p(tx_index), _i32p(n_in),
            amounts.ctypes.data_as(i64c), spk_offs.ctypes.data_as(i64c),
            _u8p(spk_blob),
        )
        return (None, int(fees[0]), int(sigops[0]), tx_index[:n], n_in[:n],
                amounts[:n], spk_offs, spk_blob)

    def coin_probes(self) -> Dict[str, int]:
        """Hash-table probes (a find, an insert or an erase by key) the last
        `accounting()` of this block and every `apply_block` of it since
        made, by table: `view`, and `block` (pass 1's table of the block's
        own coins). An accounting starts both at zero."""
        out = np.zeros(2, dtype=np.int64)
        lib().nat_block_coin_probes(
            self._ptr, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        return {"view": int(out[0]), "block": int(out[1])}

    STAGES = (("accounting", "decide"), ("accounting", "fill"), ("accounting", "copy"))

    def stages(self) -> "NativeStages":
        """What the last `accounting()` of this block spent beneath the
        ctypes boundary, `.stages[(call, stage)]` = (seconds, times
        stamped): `decide` (pass 1: everything that can refuse the block),
        `fill` (pass 2: the per-input records, the spent-output digests, the
        hash precomputes, the script cache's keys) and `copy` (the five
        arrays copied out into `accounting()`'s buffers). A block pass 1
        refused shows `fill` and `copy` unstamped. An accounting starts all
        at zero."""
        out = (ctypes.c_int64 * (2 * len(self.STAGES)))()
        lib().nat_block_stages(self._ptr, out)
        return NativeStages(NativeStages.table(self.STAGES, out), {})

    def spent_digests(self) -> np.ndarray:
        """(n_tx, 32) per-tx spent-output digests (coinbase rows zero);
        valid after a successful accounting() call."""
        out = np.zeros((self.n_tx, 32), dtype=np.uint8)
        lib().nat_block_spent_digests(self._ptr, _u8p(out))
        return out

    def script_keys(self) -> np.ndarray:
        """(n_inputs, 32) script-execution-cache keys for every
        non-coinbase input, under the salt and flags of the successful
        `accounting(..., salt=...)` call that made them (byte-identical to
        ScriptExecutionCache `_key(_parts(...))`)."""
        out = np.zeros((self.n_inputs, 32), dtype=np.uint8)
        got = lib().nat_block_script_keys(self._ptr, _u8p(out))
        if got != out.size:
            raise ValueError("no keys: accounting() was given no salt")
        return out


class NativeCoinsView:
    """Native UTXO set (native/block.hpp NView) with the models/validate.py
    CoinsView duck API plus batch insert and O(1) clone."""

    __slots__ = ("_ptr",)

    def __init__(self, _ptr=None):
        if _ptr is None:
            L = lib()
            assert L is not None
            _ptr = L.nat_view_new()
        self._ptr = _ptr

    def __del__(self):
        try:
            L = lib()
        except TypeError:
            return
        if L is not None and getattr(self, "_ptr", None):
            L.nat_view_free(self._ptr)
            self._ptr = None

    def clone(self) -> "NativeCoinsView":
        return NativeCoinsView(lib().nat_view_clone(self._ptr))

    def __deepcopy__(self, memo) -> "NativeCoinsView":
        return self.clone()

    def __len__(self) -> int:
        return int(lib().nat_view_len(self._ptr))

    def add_coins_batch(self, coins) -> None:
        """coins: sequence of (txid32, n, value, height, coinbase, spk)."""
        n = len(coins)
        if n == 0:
            return
        offs = np.zeros(n + 1, dtype=np.int64)
        np.cumsum([len(c[5]) for c in coins], out=offs[1:])
        self.add_coins_arrays(
            np.frombuffer(b"".join(c[0] for c in coins), dtype=np.uint8),
            [c[1] for c in coins], [c[2] for c in coins],
            [c[3] for c in coins], [1 if c[4] else 0 for c in coins],
            np.frombuffer(b"".join(c[5] for c in coins), dtype=np.uint8),
            offs,
        )

    def add_coins_arrays(self, txids, ns, values, heights, coinbases,
                         spk_blob, spk_offs) -> None:
        """Bulk insert from columns: `txids` is 32 bytes a coin, `ns`,
        `values`, `heights`, `coinbases` one entry a coin, and coin i's
        scriptPubKey is `spk_blob[spk_offs[i]:spk_offs[i + 1]]`."""
        ns = np.ascontiguousarray(ns, dtype=np.int32)
        n = len(ns)
        txids = np.ascontiguousarray(txids, dtype=np.uint8).reshape(-1)
        values = np.ascontiguousarray(values, dtype=np.int64)
        heights = np.ascontiguousarray(heights, dtype=np.int32)
        cbs = np.ascontiguousarray(coinbases, dtype=np.int32)
        offs = np.ascontiguousarray(spk_offs, dtype=np.int64)
        blob = np.ascontiguousarray(spk_blob, dtype=np.uint8).reshape(-1)
        if (len(txids) != 32 * n or len(values) != n or len(heights) != n
                or len(cbs) != n or len(offs) != n + 1 or offs[0] != 0
                or (np.diff(offs) < 0).any() or offs[-1] > len(blob)):
            raise ValueError("coin columns disagree in length")
        if n == 0:
            return
        if not len(blob):
            blob = np.zeros(1, np.uint8)
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib().nat_view_add_coins(
            self._ptr, n, _u8p(txids), _i32p(ns), values.ctypes.data_as(i64p),
            _i32p(heights), _i32p(cbs), _u8p(blob), offs.ctypes.data_as(i64p),
        )

    # CoinsView duck API (models/validate.py) -------------------------
    def add(self, outpoint, coin) -> None:
        self.add_coins_batch(
            [(outpoint.hash, outpoint.n, coin.out.value, coin.height,
              coin.coinbase, coin.out.script_pubkey)]
        )

    def add_tx(self, tx, height: int) -> None:
        cb = tx.is_coinbase()
        self.add_coins_batch(
            [(tx.txid, n, out.value, height, cb, out.script_pubkey)
             for n, out in enumerate(tx.vout)]
        )

    def get(self, outpoint):
        L = lib()
        txid = np.frombuffer(outpoint.hash, dtype=np.uint8)
        value = np.zeros(1, np.int64)
        height = np.zeros(1, np.int32)
        cb = np.zeros(1, np.int32)
        spk_len = np.zeros(1, np.int64)
        i64c = ctypes.POINTER(ctypes.c_int64)
        found = L.nat_view_get(
            self._ptr, _u8p(txid), outpoint.n, value.ctypes.data_as(i64c),
            _i32p(height), _i32p(cb), spk_len.ctypes.data_as(i64c),
        )
        if not found:
            return None
        spk = np.zeros(max(int(spk_len[0]), 1), np.uint8)
        L.nat_view_get_spk(self._ptr, _u8p(txid), outpoint.n, _u8p(spk))
        from .core.tx import TxOut
        from .models.validate import Coin

        return Coin(
            TxOut(int(value[0]), spk[: int(spk_len[0])].tobytes()),
            int(height[0]), bool(cb[0]),
        )

    def spend(self, outpoint):
        coin = self.get(outpoint)
        if coin is not None:
            txid = np.frombuffer(outpoint.hash, dtype=np.uint8)
            lib().nat_view_spend(self._ptr, _u8p(txid), outpoint.n)
        return coin

    def apply_block(self, blk: NativeBlock, height: int, undo: bool = False):
        """UpdateCoins over the whole block. With `undo`, returns the
        `NativeBlockUndo` that `undo_block` takes to put the view back."""
        if not undo:
            lib().nat_view_apply_block(self._ptr, blk._ptr, height)
            return None
        return NativeBlockUndo(
            lib().nat_view_apply_block_undo(self._ptr, blk._ptr, height)
        )

    def undo_block(self, blk: NativeBlock, undo: "NativeBlockUndo") -> None:
        """Take back `apply_block(blk, height, undo=True)`: the block's
        outputs go, the coins it spent return as they were (amount,
        script, height, coinbase flag). Blocks applied on top of it must
        be undone first, newest first. The record keeps its coins, so it
        puts back any view that stands where that apply left one."""
        if not lib().nat_view_undo_block(self._ptr, blk._ptr, undo._ptr):
            raise ValueError("undo record was not made from this block")

    def disconnect_block(self, blk: NativeBlock, undo: "NativeBlockUndo",
                         height: int, checked: bool = False,
                         ) -> Tuple[str, int, int, int]:
        """DisconnectBlock's view half with its checks, for a block that
        was applied at `height` with the record `undo`: `blk` is any parse
        of the same raw bytes, as Core reads block and record back from
        disk. Returns (`DISCONNECT_RESULTS` outcome, the view's probes,
        coins restored, outputs removed). `"failed"`: the record is not
        this block's (a count or an outpoint disagrees); `"unclean"`: an
        output of the block is not in the view as the block made it, or a
        coin stands where a spent one returns; the view is written on
        `"ok"` alone. The record keeps its coins. `checked`: the caller
        has `undo.matches(blk)` already (it times the two halves apart) and
        the record is not held against the block a second time."""
        out = np.zeros(3, dtype=np.int64)
        code = lib().nat_view_disconnect_block(
            self._ptr, blk._ptr, undo._ptr, height, int(bool(checked)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        return DISCONNECT_RESULTS[code], int(out[0]), int(out[1]), int(out[2])

    def digest(self) -> bytes:
        """32 bytes over every coin, independent of order: with `len`,
        equal exactly when two views hold the same coins. The native call
        cuts the map's buckets over one worker a 65,536 coins, up to the
        host's cores (a million coins take a third of a second on one)."""
        out = np.zeros(32, np.uint8)
        lib().nat_view_digest(self._ptr, _u8p(out), os.cpu_count() or 1)
        return out.tobytes()


# native/block.hpp DisconnectResult, by code (validation.h DisconnectResult).
DISCONNECT_RESULTS = ("ok", "unclean", "failed")


class NativeBlockUndo:
    """The coins one `apply_block` removed from a view (native/block.hpp
    NBlockUndo; undo.h CBlockUndo), each with its outpoint, by value: the
    record outlives the parsed block it was made from. `len` counts them."""

    __slots__ = ("_ptr",)

    def __init__(self, _ptr):
        self._ptr = _ptr

    def __del__(self):
        try:
            L = lib()
        except TypeError:
            return
        if L is not None and getattr(self, "_ptr", None):
            L.nat_undo_free(self._ptr)
            self._ptr = None

    def __len__(self) -> int:
        return int(lib().nat_undo_len(self._ptr))

    def matches(self, blk: NativeBlock) -> bool:
        """Whether this record was made from a block of `blk`'s
        transactions: their count, each one's input count, every coin's
        outpoint (DisconnectBlock's DISCONNECT_FAILED checks; the view is
        not looked at)."""
        return bool(lib().nat_undo_matches_block(self._ptr, blk._ptr))


class NativeLruSet:
    """Bounded LRU set of 32-byte digests (native/lru.hpp LruSet): where
    models/sigcache.py's caches keep their keys. Every method is one C call
    that takes the set's mutex once, with the GIL released; the five
    counters live with the set and move under the same hold."""

    __slots__ = ("_ptr", "_lib")

    COUNTERS = ("hits", "misses", "insertions", "evictions", "erases")

    def __init__(self, max_entries: int):
        L = lib()
        assert L is not None
        self._lib = L
        self._ptr = L.nat_lru_new(max_entries)
        if not self._ptr:
            raise MemoryError("nat_lru_new")

    def __del__(self):
        if getattr(self, "_ptr", None):
            self._lib.nat_lru_free(self._ptr)
            self._ptr = None

    def __len__(self) -> int:
        return int(self._lib.nat_lru_len(self._ptr))

    def probe(self, blob: bytes, n: int, erase: bool):
        """Probe the first `n` digests of `blob`, in order: a hit is erased
        or touched. Returns (present mask, hits, size afterwards)."""
        if not 0 <= 32 * n <= len(blob):
            raise ValueError(f"{n} keys asked of a {len(blob)}-byte blob")
        present = np.empty(n, dtype=bool)
        hits = ctypes.c_int64()
        size = self._lib.nat_lru_probe(
            self._ptr, _as_bytes(blob), n, bool(erase), False,
            present.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.byref(hits),
        )
        return present, hits.value, size

    def probe_one(self, key: bytes, erase: bool, fabricated: bool):
        """One probe; `fabricated` counts an absent key as a hit and leaves
        the set alone. Returns (present, size afterwards)."""
        present = ctypes.c_uint8()
        hits = ctypes.c_int64()
        size = self._lib.nat_lru_probe(
            self._ptr, _key32(key), 1, bool(erase), bool(fabricated),
            ctypes.byref(present), ctypes.byref(hits),
        )
        return bool(present.value), size

    def add(self, blob: bytes, idx: Optional[np.ndarray] = None):
        """Insert the digests of `blob` that the int64 array `idx` names, in
        its order; all of them for None. Returns (inserted, evicted, size
        afterwards)."""
        blob = _as_bytes(blob)
        n_keys = len(blob) // 32
        out = (ctypes.c_int64 * 2)()
        if idx is None:
            size = self._lib.nat_lru_add(
                self._ptr, blob, n_keys, None, n_keys, out)
        else:
            assert idx.dtype == np.int64 and idx.flags.c_contiguous
            size = self._lib.nat_lru_add(
                self._ptr, blob, n_keys,
                idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                len(idx), out)
        if size == -1:
            raise IndexError(f"an index outside the blob's {n_keys} keys")
        if size < 0:
            raise MemoryError("nat_lru_add")
        return out[0], out[1], size

    def add_one(self, key: bytes):
        return self.add(_key32(key))

    def discard(self, key: bytes):
        """Drop one key. Returns (was present, size afterwards)."""
        present = ctypes.c_int32()
        size = self._lib.nat_lru_discard(
            self._ptr, _key32(key), ctypes.byref(present))
        return bool(present.value), size

    def keys_oldest_first(self) -> List[bytes]:
        room = len(self)
        while True:
            out = np.empty((max(room, 1), 32), dtype=np.uint8)
            n = self._lib.nat_lru_keys(self._ptr, _u8p(out), room)
            if n <= room:  # else it grew between the two calls
                raw = out[:n].tobytes()
                return [raw[32 * j : 32 * j + 32] for j in range(n)]
            room = n

    def counters(self) -> Dict[str, int]:
        """The five counters, of one instant."""
        out = (ctypes.c_int64 * len(self.COUNTERS))()
        self._lib.nat_lru_counters(self._ptr, out)
        return dict(zip(self.COUNTERS, out))


def _as_bytes(blob) -> bytes:
    return blob if isinstance(blob, bytes) else bytes(blob)


def _key32(key) -> bytes:
    if len(key) != 32:
        raise ValueError(f"a cache key is 32 bytes, not {len(key)}")
    return _as_bytes(key)


class NativeSecp:
    """Object surface over the native single-check verifies (drop-in for
    the secp_host functions where a fast host-exact answer is wanted)."""

    @staticmethod
    def verify_ecdsa(pubkey: bytes, sig_der: bytes, msg32: bytes) -> bool:
        L = lib()
        assert L is not None and len(msg32) == 32
        pk = np.frombuffer(pubkey, dtype=np.uint8) if pubkey else np.zeros(1, np.uint8)
        sg = np.frombuffer(sig_der, dtype=np.uint8) if sig_der else np.zeros(1, np.uint8)
        ms = np.frombuffer(msg32, dtype=np.uint8)
        return bool(
            L.nat_verify_ecdsa(_u8p(pk), len(pubkey), _u8p(sg), len(sig_der), _u8p(ms))
        )

    @staticmethod
    def verify_schnorr(pubkey32: bytes, sig64: bytes, msg32: bytes) -> bool:
        L = lib()
        assert L is not None
        if len(pubkey32) != 32 or len(sig64) != 64 or len(msg32) != 32:
            return False
        a = np.frombuffer(pubkey32, dtype=np.uint8)
        b = np.frombuffer(sig64, dtype=np.uint8)
        c = np.frombuffer(msg32, dtype=np.uint8)
        return bool(L.nat_verify_schnorr(_u8p(a), _u8p(b), _u8p(c)))

    @staticmethod
    def tweak_add_check(
        tweaked32: bytes, parity: int, internal32: bytes, tweak32: bytes
    ) -> bool:
        L = lib()
        assert L is not None
        if len(tweaked32) != 32 or len(internal32) != 32 or len(tweak32) != 32:
            return False
        a = np.frombuffer(tweaked32, dtype=np.uint8)
        b = np.frombuffer(internal32, dtype=np.uint8)
        c = np.frombuffer(tweak32, dtype=np.uint8)
        return bool(L.nat_tweak_add_check(_u8p(a), parity & 1, _u8p(b), _u8p(c)))
