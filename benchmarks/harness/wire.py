"""The framing `serving/ingress.py` documents, written from its docstring.

All integers big-endian; a 5-byte header `type:u8 len:u32`, then the
payload. REQ 0x01 `rid:u32 tenant:u16+bytes item`; RESP 0x02 `rid:u32 ok:u8
error:u16 script_error:u16` (0xFFFF: none); ERR 0x03 `rid:u32 code:u16
reason:u16+bytes`. The item is `tx:u32+bytes input_index:u32 flags:u32
amount:i64`, then two optional tails behind u8 presence flags: `script:
u32+bytes` and `n:u16 (amount:i64 script:u32+bytes)*`. The served client
imports this module and nothing of the program, so it never loads JAX.
"""

from __future__ import annotations

import struct
from typing import List, Optional, Sequence, Tuple

FRAME_REQ, FRAME_RESP, FRAME_ERR = 1, 2, 3
HEADER_LEN = 5
NO_SCRIPT_ERROR = 0xFFFF


def encode_request(
    rid: int, tenant: str, spending_tx: bytes, input_index: int, flags: int,
    amount: int = 0, script: Optional[bytes] = None,
    spent_outputs: Optional[Sequence[Tuple[int, bytes]]] = None,
) -> bytes:
    """One whole REQ frame, header included."""
    tb = tenant.encode("utf-8")
    parts: List[bytes] = [
        struct.pack(">IH", rid, len(tb)), tb,
        struct.pack(">I", len(spending_tx)), spending_tx,
        struct.pack(">IIq", input_index, flags, amount),
    ]
    if script is None:
        parts.append(b"\x00")
    else:
        parts.append(b"\x01" + struct.pack(">I", len(script)) + script)
    if spent_outputs is None:
        parts.append(b"\x00")
    else:
        parts.append(b"\x01" + struct.pack(">H", len(spent_outputs)))
        for amt, spk in spent_outputs:
            parts.append(struct.pack(">qI", amt, len(spk)) + spk)
    payload = b"".join(parts)
    return struct.pack(">BI", FRAME_REQ, len(payload)) + payload


def decode_header(hdr: bytes) -> Tuple[int, int]:
    return struct.unpack(">BI", hdr)


def decode_response(payload: bytes) -> Tuple[int, bool, int, Optional[int]]:
    """(rid, ok, error, script_error or None)."""
    rid, ok, err, se = struct.unpack(">IBHH", payload)
    return rid, bool(ok), err, None if se == NO_SCRIPT_ERROR else se


def decode_error(payload: bytes) -> Tuple[int, int, str]:
    """(rid, code, reason); rid 0 is a session-level error."""
    rid, code, n = struct.unpack(">IHH", payload[:8])
    return rid, code, payload[8 : 8 + n].decode("utf-8", "replace")
