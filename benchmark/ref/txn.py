"""Reference parse of one wire transaction (the public Solana format).

A transaction is accepted only if every field is present, every
length is a minimal compact-u16, the header is consistent
(required signatures == signature count, fewer readonly signers than
signers, accounts enough for signers plus readonly unsigned), every
instruction's program index names a static account, a legacy message's
account indices name static accounts, the version is 0 or legacy, and
no byte trails. The limits are those of the format at the 1232 B MTU.
"""

from __future__ import annotations

MTU = 1232
MAX_SIG_CNT = 19
MAX_ACCT_CNT = 35
MAX_INSTR_CNT = 355


class ParseError(ValueError):
    pass


def _cu16(buf: bytes, off: int) -> tuple[int, int]:
    val = 0
    for i in range(3):
        if off + i >= len(buf):
            raise ParseError("compact-u16 past end")
        b = buf[off + i]
        if i == 2 and b > 3:
            raise ParseError("compact-u16 overflow")
        val |= (b & 0x7F) << (7 * i)
        if b < 0x80:
            if i and b == 0:
                raise ParseError("non-minimal compact-u16")
            return val, off + i + 1
    raise ParseError("compact-u16 overflow")


def _take(buf: bytes, off: int, n: int) -> int:
    if off + n > len(buf):
        raise ParseError("field past end")
    return off + n


def parse(buf: bytes):
    """(signatures, signer public keys, message bytes); raises ParseError."""
    if len(buf) > MTU:
        raise ParseError("larger than the MTU")
    n_sig, off = _cu16(buf, 0)
    if not 1 <= n_sig <= MAX_SIG_CNT:
        raise ParseError("signature count")
    sigs = [buf[off + 64 * i: off + 64 * (i + 1)] for i in range(n_sig)]
    off = _take(buf, off, 64 * n_sig)
    msg_off = off
    legacy = True
    if off < len(buf) and buf[off] & 0x80:
        if buf[off] & 0x7F:
            raise ParseError("unsupported version")
        legacy = False
        off += 1
    off = _take(buf, off, 3)
    n_req, n_ro_signed, n_ro_unsigned = buf[off - 3], buf[off - 2], buf[off - 1]
    if n_req != n_sig or n_ro_signed >= n_req:
        raise ParseError("header")
    n_acct, off = _cu16(buf, off)
    if not n_req <= n_acct <= MAX_ACCT_CNT or n_ro_unsigned > n_acct - n_req:
        raise ParseError("account count")
    acct_off = off
    off = _take(buf, off, 32 * n_acct)
    off = _take(buf, off, 32)  # recent blockhash
    n_instr, off = _cu16(buf, off)
    if n_instr > MAX_INSTR_CNT:
        raise ParseError("instruction count")
    for _ in range(n_instr):
        off = _take(buf, off, 1)
        if buf[off - 1] >= n_acct:
            raise ParseError("program index")
        n, off = _cu16(buf, off)
        idx = buf[off: off + n]
        off = _take(buf, off, n)
        if legacy and any(i >= n_acct for i in idx):
            raise ParseError("account index")
        n, off = _cu16(buf, off)
        off = _take(buf, off, n)
    if not legacy:
        n_lut, off = _cu16(buf, off)
        for _ in range(n_lut):
            off = _take(buf, off, 32)
            for _ in range(2):
                n, off = _cu16(buf, off)
                off = _take(buf, off, n)
    if off != len(buf):
        raise ParseError("trailing bytes")
    pubs = [buf[acct_off + 32 * i: acct_off + 32 * (i + 1)]
            for i in range(n_sig)]
    return sigs, pubs, buf[msg_off:]
