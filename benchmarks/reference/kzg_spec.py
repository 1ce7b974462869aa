"""The plain reference of the cell `kzg_6_blobs`: Deneb's blob verification
written function for function after consensus-specs
`specs/deneb/polynomial-commitments.md`, in Python integers over
`lighthouse_tpu/crypto/bls381` (the pure-Python curve, serialization and
pairing). It imports nothing from `crypto/kzg.py`, `crypto/jaxbls` or the
chain: the same bytes have to give the same verdicts by another road.

Names are the spec's. Departures, each marked where it happens:

  (a) the trusted setup is an argument (`Setup`), not a module constant: the
      verifier reads only `KZG_SETUP_G2[1]` and the roots of unity, and the
      roots are computed here (`compute_roots_of_unity`), not taken from the
      program;
  (b) an `assert` of the spec is `_require`, which raises `SpecAssertion`
      (an AssertionError that `python -O` cannot strip). A caller that wants
      a verdict reads it as False: `verdict_of`;
  (c) `bls.KeyValidate` is `serde.g1_decompress(subgroup_check=True)`: on the
      curve and in the subgroup, by multiplication with the group order; the
      infinity point is let through before it, as `validate_kzg_g1` does;
  (d) `bls.pairing_check` is `pairing.multi_pairing_is_one`, which takes no
      infinity: a pair with an infinity side contributes 1 and is left out.
"""

from __future__ import annotations

import hashlib

from lighthouse_tpu.crypto.bls381 import curve as bls
from lighthouse_tpu.crypto.bls381 import pairing, serde
from lighthouse_tpu.crypto.bls381.constants import R

BLS_MODULUS = R
BYTES_PER_COMMITMENT = 48
BYTES_PER_PROOF = 48
BYTES_PER_FIELD_ELEMENT = 32
G1_POINT_AT_INFINITY = b"\xc0" + b"\x00" * 47
KZG_ENDIANNESS = "big"
PRIMITIVE_ROOT_OF_UNITY = 7
FIAT_SHAMIR_PROTOCOL_DOMAIN = b"FSBLOBVERIFY_V1_"
RANDOM_CHALLENGE_KZG_BATCH_DOMAIN = b"RCKZGBATCH___V1_"


class SpecAssertion(AssertionError):
    """An `assert` of the spec failed: the input is invalid."""


def _require(cond: bool, what: str) -> None:          # departure (b)
    if not cond:
        raise SpecAssertion(what)


def verdict_of(fn, *args) -> bool:
    """`fn(*args)`, an invalid input read as False."""
    try:
        return bool(fn(*args))
    except SpecAssertion:
        return False


# --- bit-reversal permutation


def is_power_of_two(value: int) -> bool:
    return (value > 0) and (value & (value - 1) == 0)


def reverse_bits(n: int, order: int) -> int:
    _require(is_power_of_two(order), "order is no power of two")
    return int(("{:0" + str(order.bit_length() - 1) + "b}").format(n)[::-1], 2)


def bit_reversal_permutation(sequence):
    return [sequence[reverse_bits(i, len(sequence))] for i in range(len(sequence))]


# --- BLS12-381 helpers


def hash_to_bls_field(data: bytes) -> int:
    hashed_data = hashlib.sha256(data).digest()
    return int.from_bytes(hashed_data, KZG_ENDIANNESS) % BLS_MODULUS


def bytes_to_bls_field(b: bytes) -> int:
    field_element = int.from_bytes(b, KZG_ENDIANNESS)
    _require(field_element < BLS_MODULUS, "field element is not canonical")
    return field_element


def validate_kzg_g1(b: bytes) -> None:
    if b == G1_POINT_AT_INFINITY:
        return
    try:                                               # departure (c)
        serde.g1_decompress(b, subgroup_check=True)
    except serde.DecodeError as e:
        raise SpecAssertion(f"KeyValidate: {e}") from e


def bytes_to_kzg_commitment(b: bytes):
    _require(len(b) == BYTES_PER_COMMITMENT, "commitment length")
    validate_kzg_g1(b)
    return serde.g1_decompress(b, subgroup_check=False)


def bytes_to_kzg_proof(b: bytes):
    _require(len(b) == BYTES_PER_PROOF, "proof length")
    validate_kzg_g1(b)
    return serde.g1_decompress(b, subgroup_check=False)


def g1_lincomb(points, scalars):
    _require(len(points) == len(scalars), "lincomb lengths")
    result = None                                      # bls.Z1()
    for x, a in zip(points, scalars):
        result = bls.g1_add(result, bls.g1_mul(x, a))
    return result


def compute_powers(x: int, n: int) -> list:
    current_power = 1
    powers = []
    for _ in range(n):
        powers.append(current_power)
        current_power = current_power * x % BLS_MODULUS
    return powers


def compute_roots_of_unity(order: int) -> list:
    _require((BLS_MODULUS - 1) % order == 0, "order does not divide r - 1")
    root_of_unity = pow(PRIMITIVE_ROOT_OF_UNITY, (BLS_MODULUS - 1) // order,
                        BLS_MODULUS)
    return compute_powers(root_of_unity, order)


class Setup:                                           # departure (a)
    """What the verifier reads of the trusted setup."""

    def __init__(self, field_elements_per_blob: int, kzg_setup_g2_1):
        self.FIELD_ELEMENTS_PER_BLOB = field_elements_per_blob
        self.BYTES_PER_BLOB = BYTES_PER_FIELD_ELEMENT * field_elements_per_blob
        self.KZG_SETUP_G2_1 = kzg_setup_g2_1
        self.roots_of_unity_brp = bit_reversal_permutation(
            compute_roots_of_unity(field_elements_per_blob))


# --- polynomials


def blob_to_polynomial(blob: bytes, setup: Setup) -> list:
    polynomial = []
    for i in range(setup.FIELD_ELEMENTS_PER_BLOB):
        polynomial.append(bytes_to_bls_field(
            blob[i * BYTES_PER_FIELD_ELEMENT:(i + 1) * BYTES_PER_FIELD_ELEMENT]))
    return polynomial


def compute_challenge(blob: bytes, commitment: bytes, setup: Setup) -> int:
    degree_poly = setup.FIELD_ELEMENTS_PER_BLOB.to_bytes(16, KZG_ENDIANNESS)
    data = FIAT_SHAMIR_PROTOCOL_DOMAIN + degree_poly
    data += blob
    data += commitment
    return hash_to_bls_field(data)


def bls_modular_inverse(x: int) -> int:
    _require(x % BLS_MODULUS != 0, "inverse of zero")
    return pow(x, -1, BLS_MODULUS)


def div(x: int, y: int) -> int:
    return x * bls_modular_inverse(y) % BLS_MODULUS


def evaluate_polynomial_in_evaluation_form(polynomial, z: int,
                                           setup: Setup) -> int:
    width = len(polynomial)
    _require(width == setup.FIELD_ELEMENTS_PER_BLOB, "polynomial width")
    inverse_width = bls_modular_inverse(width)
    roots_of_unity_brp = setup.roots_of_unity_brp

    # If we are asked to evaluate within the domain, we already know the answer
    if z in roots_of_unity_brp:
        eval_index = roots_of_unity_brp.index(z)
        return polynomial[eval_index]

    result = 0
    for i in range(width):
        a = polynomial[i] * roots_of_unity_brp[i] % BLS_MODULUS
        b = (BLS_MODULUS + z - roots_of_unity_brp[i]) % BLS_MODULUS
        result += div(a, b)
    result = result * (pow(z, width, BLS_MODULUS) - 1) * inverse_width
    return result % BLS_MODULUS


# --- KZG


def _pairing_check(pairs) -> bool:                     # departure (d)
    live = [(p, q) for p, q in pairs if p is not None and q is not None]
    return not live or pairing.multi_pairing_is_one(live)


def verify_kzg_proof_impl(commitment, z: int, y: int, proof,
                          setup: Setup) -> bool:
    # Verify: P - y = Q * (X - z)
    X_minus_z = bls.g2_add(
        setup.KZG_SETUP_G2_1,
        bls.g2_mul(bls.G2_GEN, (BLS_MODULUS - z) % BLS_MODULUS))
    P_minus_y = bls.g1_add(
        commitment, bls.g1_mul(bls.G1_GEN, (BLS_MODULUS - y) % BLS_MODULUS))
    return _pairing_check([
        [P_minus_y, bls.g2_neg(bls.G2_GEN)],
        [proof, X_minus_z],
    ])


def verify_kzg_proof_batch(commitments, zs, ys, proofs, setup: Setup,
                           commitments_bytes, proofs_bytes) -> bool:
    _require(len(commitments) == len(zs) == len(ys) == len(proofs),
             "batch lengths")

    # Compute a random challenge. Note that it does not have to be computed
    # from a hash, r just has to be random.
    degree_poly = setup.FIELD_ELEMENTS_PER_BLOB.to_bytes(8, KZG_ENDIANNESS)
    num_commitments = len(commitments).to_bytes(8, KZG_ENDIANNESS)
    data = RANDOM_CHALLENGE_KZG_BATCH_DOMAIN + degree_poly + num_commitments

    # Append all inputs to the transcript before we hash
    for commitment, z, y, proof in zip(commitments_bytes, zs, ys, proofs_bytes):
        data += commitment \
            + z.to_bytes(BYTES_PER_FIELD_ELEMENT, KZG_ENDIANNESS) \
            + y.to_bytes(BYTES_PER_FIELD_ELEMENT, KZG_ENDIANNESS) \
            + proof

    r = hash_to_bls_field(data)
    r_powers = compute_powers(r, len(commitments))

    # Verify: e(sum r^i proof_i, [s]) ==
    # e(sum r^i (commitment_i - [y_i]) + sum r^i z_i proof_i, [1])
    proof_lincomb = g1_lincomb(proofs, r_powers)
    proof_z_lincomb = g1_lincomb(
        proofs, [z * r_power % BLS_MODULUS for z, r_power in zip(zs, r_powers)])
    C_minus_ys = [
        bls.g1_add(commitment,
                   bls.g1_mul(bls.G1_GEN, (BLS_MODULUS - y) % BLS_MODULUS))
        for commitment, y in zip(commitments, ys)
    ]
    C_minus_y_lincomb = g1_lincomb(C_minus_ys, r_powers)

    return _pairing_check([
        [proof_lincomb, bls.g2_neg(setup.KZG_SETUP_G2_1)],
        [bls.g1_add(C_minus_y_lincomb, proof_z_lincomb), bls.G2_GEN],
    ])


def verify_blob_kzg_proof(blob: bytes, commitment_bytes: bytes,
                          proof_bytes: bytes, setup: Setup) -> bool:
    _require(len(blob) == setup.BYTES_PER_BLOB, "blob length")
    _require(len(commitment_bytes) == BYTES_PER_COMMITMENT, "commitment length")
    _require(len(proof_bytes) == BYTES_PER_PROOF, "proof length")

    commitment = bytes_to_kzg_commitment(commitment_bytes)

    polynomial = blob_to_polynomial(blob, setup)
    evaluation_challenge = compute_challenge(blob, commitment_bytes, setup)

    # Evaluate polynomial at `evaluation_challenge`
    y = evaluate_polynomial_in_evaluation_form(polynomial, evaluation_challenge,
                                               setup)

    # Verify proof
    proof = bytes_to_kzg_proof(proof_bytes)
    return verify_kzg_proof_impl(commitment, evaluation_challenge, y, proof,
                                 setup)


def verify_blob_kzg_proof_batch(blobs, commitments_bytes, proofs_bytes,
                                setup: Setup) -> bool:
    _require(len(blobs) == len(commitments_bytes) == len(proofs_bytes),
             "batch lengths")

    commitments, evaluation_challenges, ys, proofs = [], [], [], []
    for blob, commitment_bytes, proof_bytes in zip(blobs, commitments_bytes,
                                                   proofs_bytes):
        _require(len(blob) == setup.BYTES_PER_BLOB, "blob length")
        _require(len(commitment_bytes) == BYTES_PER_COMMITMENT,
                 "commitment length")
        _require(len(proof_bytes) == BYTES_PER_PROOF, "proof length")
        commitment = bytes_to_kzg_commitment(commitment_bytes)
        commitments.append(commitment)
        polynomial = blob_to_polynomial(blob, setup)
        evaluation_challenge = compute_challenge(blob, commitment_bytes, setup)
        evaluation_challenges.append(evaluation_challenge)
        ys.append(evaluate_polynomial_in_evaluation_form(
            polynomial, evaluation_challenge, setup))
        proofs.append(bytes_to_kzg_proof(proof_bytes))

    return verify_kzg_proof_batch(commitments, evaluation_challenges, ys,
                                  proofs, setup, commitments_bytes,
                                  proofs_bytes)
