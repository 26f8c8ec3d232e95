"""Reference Ed25519 verification with the validator's strictness rules.

Status codes (the ones the verify engine returns per lane):

    0  SUCCESS
   -1  ERR_SIG     s >= L, or small-order R
   -2  ERR_PUBKEY  A or R does not decode, or A has small order
   -3  ERR_MSG     the equation fails (the signature is not over M)

Checks run in this order, and the first that fails decides the status:

    1. s < L                                   else ERR_SIG
    2. A and R decode (RFC 8032 5.1.3)         else ERR_PUBKEY
    3. A is not of small order                 else ERR_PUBKEY
    4. R is not of small order                 else ERR_SIG
    5. [s]B == R + [SHA-512(R || A || M)]A     else ERR_MSG

Steps 1 to 4 are written out below in plain integer arithmetic; step 5
is OpenSSL's RFC 8032 verify, which additionally requires R's encoding
to be the canonical one. Honest signatures always encode R canonically,
so the two agree on every signature the generators make (a flipped
byte lands R on a canonical encoding of another point but for a
2^-250 chance).
"""

from __future__ import annotations

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

SUCCESS = 0
ERR_SIG = -1
ERR_PUBKEY = -2
ERR_MSG = -3

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = (-121665 * pow(121666, P - 2, P)) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)


def decode_point(enc: bytes):
    """Extended coordinates (X, Y, Z, T) of a 32-byte encoding, or None
    where RFC 8032 5.1.3 rejects it (y >= p, no square root, x = 0 with
    the sign bit set)."""
    n = int.from_bytes(enc, "little")
    sign = n >> 255
    y = n & ((1 << 255) - 1)
    if y >= P:
        return None
    u = (y * y - 1) % P
    v = (D * y * y + 1) % P
    x = (u * pow(v, 3, P) * pow(u * pow(v, 7, P), (P - 5) // 8, P)) % P
    vx2 = (v * x * x) % P
    if vx2 == u:
        pass
    elif vx2 == (-u) % P:
        x = (x * SQRT_M1) % P
    else:
        return None
    if x == 0 and sign:
        return None
    if (x & 1) != sign:
        x = P - x
    return (x, y, 1, (x * y) % P)


def _double(p):
    x1, y1, z1, _ = p
    a = x1 * x1 % P
    b = y1 * y1 % P
    c = 2 * z1 * z1 % P
    h = (a + b) % P
    e = (h - (x1 + y1) * (x1 + y1)) % P
    g = (a - b) % P
    f = (c + g) % P
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def is_small_order(p) -> bool:
    """[8]P is the identity (X == 0 and Y == Z)."""
    for _ in range(3):
        p = _double(p)
    return p[0] % P == 0 and (p[1] - p[2]) % P == 0


def status(msg: bytes, sig: bytes, pub: bytes) -> int:
    """The status of one (message, signature, public key) lane."""
    if len(sig) != 64 or len(pub) != 32:
        return ERR_SIG
    if int.from_bytes(sig[32:], "little") >= L:
        return ERR_SIG
    a = decode_point(pub)
    r = decode_point(sig[:32])
    if a is None or r is None:
        return ERR_PUBKEY
    if is_small_order(a):
        return ERR_PUBKEY
    if is_small_order(r):
        return ERR_SIG
    try:
        Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
    except InvalidSignature:
        return ERR_MSG
    return SUCCESS
