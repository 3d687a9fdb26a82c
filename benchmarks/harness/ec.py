"""secp256k1 signing for the traffic generators, independent of the program.

The program's `crypto/secp_host.py` signs by plain double-and-add (7 ms an
input, PR 21's 73 s for 10,000 inputs). Every run of every check pays the
generator in `setup_s`, so the benchmark signs with a fixed-base table of
its own: k*G is 32 mixed additions. Plain Python integers, `hashlib` only.
Nothing here runs inside a measured window.
"""

from __future__ import annotations

import hashlib
from typing import List, Optional, Tuple

P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8

_WINDOW = 8
_Jac = Tuple[int, int, int]


def _dbl(p: _Jac) -> _Jac:
    x, y, z = p
    if not y:
        return (0, 1, 0)
    s = 4 * x * y * y % P
    m = 3 * x * x % P
    x3 = (m * m - 2 * s) % P
    return (x3, (m * (s - x3) - 8 * pow(y, 4, P)) % P, 2 * y * z % P)


def _add_affine(p: _Jac, ax: int, ay: int) -> _Jac:
    """Jacobian `p` plus the affine point (ax, ay)."""
    x1, y1, z1 = p
    if not z1:
        return (ax, ay, 1)
    z2 = z1 * z1 % P
    u2 = ax * z2 % P
    s2 = ay * z2 * z1 % P
    if u2 == x1:
        return _dbl(p) if s2 == y1 else (0, 1, 0)
    h = (u2 - x1) % P
    r = (s2 - y1) % P
    h2 = h * h % P
    h3 = h2 * h % P
    v = x1 * h2 % P
    x3 = (r * r - h3 - 2 * v) % P
    return (x3, (r * (v - x3) - y1 * h3) % P, z1 * h % P)


def _affine(p: _Jac) -> Tuple[int, int]:
    zi = pow(p[2], -1, P)
    z2 = zi * zi % P
    return p[0] * z2 % P, p[1] * z2 * zi % P


def _batch_affine(points: List[_Jac]) -> List[Tuple[int, int]]:
    """Affine forms with one inversion (Montgomery's trick)."""
    acc, run = 1, []
    for _, _, z in points:
        acc = acc * z % P
        run.append(acc)
    inv = pow(acc, -1, P)
    out: List[Optional[Tuple[int, int]]] = [None] * len(points)
    for i in range(len(points) - 1, -1, -1):
        x, y, z = points[i]
        zi = inv * (run[i - 1] if i else 1) % P
        inv = inv * z % P
        z2 = zi * zi % P
        out[i] = (x * z2 % P, y * z2 * zi % P)
    return out  # type: ignore[return-value]


def _build_table() -> List[List[Tuple[int, int]]]:
    """table[w][d-1] = d * 2**(8w) * G, affine."""
    rows: List[List[_Jac]] = []
    base = (GX, GY)
    for _ in range(256 // _WINDOW):
        row: List[_Jac] = [(base[0], base[1], 1)]
        for _d in range(2, 1 << _WINDOW):
            row.append(_add_affine(row[-1], *base))
        rows.append(row)
        top = _add_affine(row[-1], *base)  # 256 * base
        base = _affine(top)
    flat = _batch_affine([p for row in rows for p in row])
    width = (1 << _WINDOW) - 1
    return [flat[i * width : (i + 1) * width] for i in range(len(rows))]


_TABLE: Optional[List[List[Tuple[int, int]]]] = None


def g_mul(k: int) -> Tuple[int, int]:
    """k*G as an affine point, 0 < k < N."""
    global _TABLE
    if _TABLE is None:
        _TABLE = _build_table()
    acc: _Jac = (0, 1, 0)
    for w in range(256 // _WINDOW):
        d = (k >> (_WINDOW * w)) & ((1 << _WINDOW) - 1)
        if d:
            acc = _add_affine(acc, *_TABLE[w][d - 1])
    return _affine(acc)


def tagged_hash(tag: str, data: bytes) -> bytes:
    t = hashlib.sha256(tag.encode()).digest()
    return hashlib.sha256(t + t + data).digest()


def pubkey_create(seckey: int) -> bytes:
    x, y = g_mul(seckey)
    return bytes([2 + (y & 1)]) + x.to_bytes(32, "big")


def xonly_pubkey_create(seckey: int) -> Tuple[bytes, int]:
    x, y = g_mul(seckey)
    return x.to_bytes(32, "big"), y & 1


def _der_int(v: int) -> bytes:
    raw = v.to_bytes((v.bit_length() + 7) // 8 or 1, "big")
    if raw[0] & 0x80:
        raw = b"\x00" + raw
    return b"\x02" + bytes([len(raw)]) + raw


def sign_ecdsa(seckey: int, msg32: bytes) -> bytes:
    """Deterministic low-s ECDSA, strict DER, no hashtype byte."""
    m = int.from_bytes(msg32, "big") % N
    counter = 0
    while True:
        k = int.from_bytes(
            hashlib.sha256(
                seckey.to_bytes(32, "big") + msg32 + counter.to_bytes(4, "big")
            ).digest(),
            "big",
        ) % N
        counter += 1
        if not k:
            continue
        r = g_mul(k)[0] % N
        s = pow(k, -1, N) * (m + r * seckey) % N
        if not r or not s:
            continue
        if s > N // 2:
            s = N - s
        body = _der_int(r) + _der_int(s)
        return b"\x30" + bytes([len(body)]) + body


def sign_schnorr(seckey: int, msg32: bytes) -> bytes:
    """BIP340 signature with the all-zero auxiliary randomness."""
    px, py = g_mul(seckey)
    d = seckey if not py & 1 else N - seckey
    t = d ^ int.from_bytes(tagged_hash("BIP0340/aux", b"\x00" * 32), "big")
    pxb = px.to_bytes(32, "big")
    k0 = int.from_bytes(
        tagged_hash("BIP0340/nonce", t.to_bytes(32, "big") + pxb + msg32), "big"
    ) % N
    if not k0:
        raise ValueError("BIP340 nonce is zero")
    rx, ry = g_mul(k0)
    k = k0 if not ry & 1 else N - k0
    rxb = rx.to_bytes(32, "big")
    e = int.from_bytes(
        tagged_hash("BIP0340/challenge", rxb + pxb + msg32), "big"
    ) % N
    return rxb + ((k + e * d) % N).to_bytes(32, "big")
