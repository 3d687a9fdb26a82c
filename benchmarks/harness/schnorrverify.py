"""BIP 340 verification and the x-only tweak check for the plain references,
independent of the program.

`harness/ec.py` signs; this checks a (key, signature, message) triple and a
taproot output key against its internal key and tweak, as BIP 340
("Verification", `lift_x`) and BIP 341 (`taproot_tweak_pubkey`, the rule
for spending by script path) write them, with `harness/ec.py`'s plain
Python integers and none of the program's curve code. Nothing here runs
inside a measured window.
"""

from __future__ import annotations

from typing import Optional, Tuple

from .ec import N, P, _add_affine, _affine, g_mul, tagged_hash
from .ecverify import _mul

Point = Tuple[int, int]


def lift_x(x: int) -> Optional[Point]:
    """The point of x-coordinate `x` whose y is even, or None (BIP 340)."""
    if x >= P:
        return None
    y2 = (pow(x, 3, P) + 7) % P
    y = pow(y2, (P + 1) // 4, P)
    if y * y % P != y2:
        return None
    return x, (y if not y & 1 else P - y)


def verify_schnorr(key32: bytes, sig64: bytes, msg: bytes) -> bool:
    """BIP 340 `Verify(pk, m, sig)`: R = s*G - e*P has an even y and the
    x-coordinate r."""
    if len(key32) != 32 or len(sig64) != 64:
        return False
    point = lift_x(int.from_bytes(key32, "big"))
    r = int.from_bytes(sig64[:32], "big")
    s = int.from_bytes(sig64[32:], "big")
    if point is None or r >= P or s >= N:
        return False
    e = int.from_bytes(tagged_hash("BIP0340/challenge", sig64[:32] + key32 + msg), "big") % N
    total = _mul((N - e) % N, *point) if e else (0, 1, 0)
    if s:
        total = _add_affine(total, *g_mul(s))
    if not total[2]:
        return False
    x, y = _affine(total)
    return not y & 1 and x == r


def tweak_add_check(output32: bytes, parity: int, internal32: bytes, tweak32: bytes) -> bool:
    """BIP 341: Q = lift_x(p) + t*G, with t < n, has the x-coordinate
    `output32` and a y of the control block's parity bit."""
    if len(output32) != 32 or len(internal32) != 32 or len(tweak32) != 32:
        return False
    point = lift_x(int.from_bytes(internal32, "big"))
    t = int.from_bytes(tweak32, "big")
    if point is None or t >= N:
        return False
    total = (point[0], point[1], 1)
    if t:
        total = _add_affine(total, *g_mul(t))
    if not total[2]:
        return False
    x, y = _affine(total)
    return x == int.from_bytes(output32, "big") and (y & 1) == parity
