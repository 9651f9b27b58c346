"""The benchmark's BLS12-381 against the system's own signer and CPU tier
(two independent implementations of the same specification)."""

import random

import bls12 as B


def test_sign_and_pubkey_match_the_system_signer():
    from lodestar_tpu import native
    from lodestar_tpu.bls import api

    rng = random.Random(2**31 + 5)
    for _ in range(4):
        sk, msg = rng.randrange(1, B.R), rng.randbytes(32)
        rc, sig = native.bls_sign(sk.to_bytes(32, "big"), msg, api.DST_G2)
        assert rc == 0 and B.sign(sk, msg) == sig
        assert B.pubkey(sk) == api.SecretKey(sk).to_public_key().to_bytes()


def test_reference_verdict_rejects_what_the_cpu_tier_rejects():
    from lodestar_tpu.bls import api
    from lodestar_tpu.chain.bls_verifier import CpuBlsVerifier

    sk, msg = 12345, bytes(range(32))
    pk, sig = B.pubkey(sk), B.sign(sk, msg)
    other = B.sign(sk + 1, msg)
    cases = [(pk, msg, sig), (pk, msg, other), (pk, bytes(32), sig)]
    ref = [B.reference_verdict(sk, *c) for c in cases]
    sets = [api.SignatureSet(pubkey=api.PublicKey.from_bytes(p), message=m,
                             signature=s) for p, m, s in cases]
    assert ref == CpuBlsVerifier().verify_signature_sets_individual(sets)
    assert ref == [True, False, False]


def test_consecutive_sums_are_multiples():
    h = B.hash_to_g2(b"\x07" * 32)
    acc = B.g2_mul_affine(h, 10)
    acc = B.g2_add_affine(acc, h)
    assert B.g2_normalize_many([acc]) == B.g2_normalize_many(
        [B.g2_mul_affine(h, 11)])
