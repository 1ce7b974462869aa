"""The plain reference of `mainnet-subnet-att-1key`: batch verification of
unaggregated attestations, each ONE signature by ONE validator's key on a
32-byte message that a whole committee shares, and the minting of a slot's
such signatures from the derived registry.

It stands on `bls_registry_spec.py` beside it (the fields, both curves,
hash-to-G2, the Miller loop and the final exponentiation in Python
integers) and, like it, imports nothing of the program under test. What is
its own is stated here as the consensus spec states it
(`specs/phase0/beacon-chain.md` `is_valid_indexed_attestation` with one
attesting index -> `bls.FastAggregateVerify([pk], m, sig)` = `bls.Verify`):

    one set:    e(pk, H(m)) == e(G1, sig)
    a batch:    e(-G1, sum_i z_i sig_i) * prod_i e(z_i pk_i, H(m_i)) == 1

with 64-bit nonzero z_i, ONE pair a set whatever messages the sets share
(Lighthouse `verify_signature_sets`, blst.rs:40-120, as
`attestation_verification/batch.rs:139-225` calls it for unaggregated
attestations). No set is merged with another that signs the same message:
H(m) of an equal message is looked up, not recomputed, and that is all the
sharing there is. There is no key sum (a set has one key), no limb, no
table on any device, no padding bucket.

Minting. Validator i's secret key is a + i d (mod r), so its signature on
M is (a + i d) H(M) = A + i D with A = a H(M) and D = d H(M): two G2
multiplications a message, and for a committee's members one addition
each, read from two chains of additions (A + j D for the low half of i's
bits, (j 2^k) D for the high half). The pool file holds A and D a message
(`data/gen_subnet_pool.py`); this is the one derivation of the signatures,
the generator's and the driver's.
"""

from __future__ import annotations

import importlib.util
import os


def _load_base():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bls_registry_spec.py")
    spec = importlib.util.spec_from_file_location("bls_registry_spec", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


base = _load_base()
P, R, G1 = base.P, base.R, base.G1


# ---------------------------------------------------------- the verdicts


def _key(key_bytes: bytes, decompressed: dict):
    key_bytes = bytes(key_bytes)
    if key_bytes not in decompressed:
        decompressed[key_bytes] = base.decompress_key(key_bytes)
    return decompressed[key_bytes]


def _hashed(message: bytes, hashed: dict):
    message = bytes(message)
    if message not in hashed:
        hashed[message] = base.hash_to_g2(message)
    return hashed[message]


def verify_one(sig, key_bytes: bytes, message: bytes, decompressed=None,
               hashed=None) -> bool:
    """e(pk, H(m)) == e(G1, sig) for one unaggregated attestation: `sig` an
    affine G2 point, `key_bytes` the signer's 48 compressed bytes."""
    if sig is None:
        return False
    pk = _key(key_bytes, {} if decompressed is None else decompressed)
    h = _hashed(message, {} if hashed is None else hashed)
    return base.pairing_product_is_one([(pk, h), (base.g1_neg(G1), sig)])


def verify_batch(sets, coefficients, decompressed=None, hashed=None) -> bool:
    """`sets`: [(signature as an affine G2 point, the signer's 48-byte key,
    32-byte message), ...]; `coefficients`: one nonzero 64-bit integer a
    set. One Miller loop a set and one for the summed signatures, one final
    exponentiation. `decompressed` ({key bytes: point}) and `hashed`
    ({message: H(message)}) may be handed to several calls."""
    decompressed = {} if decompressed is None else decompressed
    hashed = {} if hashed is None else hashed
    if not sets or len(sets) != len(coefficients):
        raise ValueError("one coefficient a set, at least one set")
    f = base.F12_ONE
    sig_acc = None
    for (sig, key_bytes, message), z in zip(sets, coefficients):
        if not 0 < z < 1 << 64:
            raise ValueError("coefficients are nonzero and 64-bit")
        if sig is None:
            return False
        pk = _key(key_bytes, decompressed)
        f = base.f12_mul(f, base.miller_loop(base.g1_mul(pk, z),
                                             _hashed(message, hashed)))
        sig_acc = base.g2_add(sig_acc, base.g2_mul(sig, z))
    if sig_acc is not None:
        f = base.f12_mul(f, base.miller_loop(base.g1_neg(G1), sig_acc))
    return base.f12_pow(f, base.FINAL_EXPONENT) == base.F12_ONE


# ------------------------------------------------------------- the minting


def message_points(message: bytes, a: int, d: int) -> tuple:
    """(A, D) = (a H(M), d H(M)): validator i signs M with A + i D."""
    h = base.hash_to_g2(message)
    return base.g2_mul(h, a % R), base.g2_mul(h, d % R)


def sign_member(A, D, index: int):
    """Validator `index`'s signature on the message of (A, D), the long way
    round: one multiplication and one addition."""
    return base.g2_add(A, base.g2_mul(D, index))


def _chain(first, step, count: int) -> list:
    """[first, first + step, first + 2 step, ...], `count` entries."""
    out = [first]
    for _ in range(count - 1):
        out.append(base.g2_add(out[-1], step))
    return out


def mint_members(A, D, indices, registry_size: int) -> list:
    """[A + i D for i in indices], i < registry_size. Few members: one
    multiplication each. A committee: the two chains A + j D (j < 2^k) and
    (j 2^k) D, k half the bits of an index, then ONE addition a member."""
    indices = [int(i) for i in indices]
    if any(not 0 <= i < registry_size for i in indices):
        raise ValueError("a member is a validator of the registry")
    bits = max(registry_size - 1, 1).bit_length()
    k = (bits + 1) // 2
    if len(indices) * bits < 2 << k:
        return [sign_member(A, D, i) for i in indices]
    low = _chain(A, D, 1 << k)
    step = base.g2_mul(D, 1 << k)
    high = _chain(None, step, 1 << (bits - k))
    return [base.g2_add(low[i & ((1 << k) - 1)], high[i >> k])
            for i in indices]
