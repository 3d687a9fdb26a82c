"""ECDSA verification for the plain references, independent of the program.

`harness/ec.py` signs; this checks a (public key, signature, message)
pairing with the same plain Python integers and none of the program's
curve code, so that a reference can be its own pairing oracle. Only what
the benchmark's own traffic holds: compressed keys, strict-DER signatures.
Nothing here runs inside a measured window.
"""

from __future__ import annotations

from typing import Optional, Tuple

from .ec import N, P, _add_affine, _affine, _dbl, g_mul


def decompress(pub: bytes) -> Optional[Tuple[int, int]]:
    if len(pub) != 33 or pub[0] not in (2, 3):
        return None
    x = int.from_bytes(pub[1:], "big")
    if x >= P:
        return None
    y2 = (pow(x, 3, P) + 7) % P
    y = pow(y2, (P + 1) // 4, P)
    if y * y % P != y2:
        return None
    return x, (y if (y & 1) == (pub[0] & 1) else P - y)


def parse_der(sig: bytes) -> Optional[Tuple[int, int]]:
    """(r, s) of a strict-DER signature without its hashtype byte."""
    if len(sig) < 8 or sig[0] != 0x30 or sig[1] != len(sig) - 2 or sig[2] != 0x02:
        return None
    len_r = sig[3]
    if 4 + len_r + 2 > len(sig) or sig[4 + len_r] != 0x02:
        return None
    len_s = sig[5 + len_r]
    if 6 + len_r + len_s != len(sig):
        return None
    return (int.from_bytes(sig[4 : 4 + len_r], "big"),
            int.from_bytes(sig[6 + len_r :], "big"))


def _mul(k: int, x: int, y: int):
    """k * (x, y), Jacobian, by double-and-add."""
    acc = (0, 1, 0)
    for bit in bin(k)[2:]:
        acc = _dbl(acc)
        if bit == "1":
            acc = _add_affine(acc, x, y)
    return acc


def verify_ecdsa(pub: bytes, sig_der: bytes, msg32: bytes) -> bool:
    point, rs = decompress(pub), parse_der(sig_der)
    if point is None or rs is None:
        return False
    r, s = rs
    if not (0 < r < N and 0 < s < N):
        return False
    w = pow(s, -1, N)
    u1 = int.from_bytes(msg32, "big") * w % N
    u2 = r * w % N
    total = _mul(u2, *point)
    if u1:
        total = _add_affine(total, *g_mul(u1))
    if not total[2]:
        return False
    return _affine(total)[0] % N == r

