"""Solana wire transactions, built from scratch (legacy and v0 messages).

Layout (the public Solana transaction format):

    compact-u16 signature count, then 64 B per signature
    message: [0x80 | version  (v0 only)]
             u8 num_required_signatures, u8 num_readonly_signed,
             u8 num_readonly_unsigned
             compact-u16 account count, 32 B per account
             32 B recent blockhash
             compact-u16 instruction count, per instruction:
                 u8 program id index, compact-u16 n + n account indices,
                 compact-u16 n + n data bytes
             [v0 only] compact-u16 lookup-table count, per table:
                 32 B table key, compact-u16 n + n writable indices,
                 compact-u16 n + n readonly indices

Signatures are Ed25519 (RFC 8032) over the message bytes, made with
OpenSSL through the `cryptography` package.
"""

from __future__ import annotations

from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey
from cryptography.hazmat.primitives.serialization import Encoding, PublicFormat

MTU = 1232  # largest transaction a TPU port accepts (one UDP/QUIC datagram)
COMPUTE_BUDGET_PROGRAM = bytes.fromhex(
    # base58 ComputeBudget111111111111111111111111111111, decoded.
    "0306466fe5211732ffecadba72c39be7bc8ce5bbc5f7126b2c439b3a40000000")


def compact_u16(val: int) -> bytes:
    if not 0 <= val <= 0xFFFF:
        raise ValueError(f"compact-u16 out of range: {val}")
    out = bytearray()
    while True:
        b = val & 0x7F
        val >>= 7
        if val:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def keypair(seed: bytes) -> tuple[Ed25519PrivateKey, bytes]:
    key = Ed25519PrivateKey.from_private_bytes(seed)
    pub = key.public_key().public_bytes(Encoding.Raw, PublicFormat.Raw)
    return key, pub


def message(signer_pubs: list[bytes], extra_accounts: list[bytes],
            n_readonly_unsigned: int, blockhash: bytes,
            instrs: list[tuple[int, list[int], bytes]],
            lookup_tables: list[tuple[bytes, list[int], list[int]]] | None,
            ) -> bytes:
    """The message bytes; lookup_tables=None makes a legacy message."""
    accounts = list(signer_pubs) + list(extra_accounts)
    msg = bytearray()
    if lookup_tables is not None:
        msg.append(0x80)  # version 0
    msg += bytes([len(signer_pubs), 0, n_readonly_unsigned])
    msg += compact_u16(len(accounts))
    for a in accounts:
        msg += a
    msg += blockhash
    msg += compact_u16(len(instrs))
    for prog, accs, data in instrs:
        msg.append(prog)
        msg += compact_u16(len(accs)) + bytes(accs)
        msg += compact_u16(len(data)) + data
    if lookup_tables is not None:
        msg += compact_u16(len(lookup_tables))
        for key, wr, ro in lookup_tables:
            msg += key
            msg += compact_u16(len(wr)) + bytes(wr)
            msg += compact_u16(len(ro)) + bytes(ro)
    return bytes(msg)


def transaction(keys: list[Ed25519PrivateKey], msg: bytes) -> bytes:
    out = bytearray(compact_u16(len(keys)))
    for k in keys:
        out += k.sign(msg)
    out += msg
    return bytes(out)
