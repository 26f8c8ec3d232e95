"""The plain reference the benchmark judges `correct` by.

It imports nothing of the program under test: Ed25519 verification is
OpenSSL (through `cryptography`) plus the point checks written out here
in plain Python, and transaction parsing follows the public wire format.
"""
