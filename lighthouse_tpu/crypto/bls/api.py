"""Top-level BLS operations + runtime backend registry.

The multi-set verification equation (matching blst's
verify_multiple_aggregate_signatures as used in
/root/reference/crypto/bls/src/impls/blst.rs:35-117): with random nonzero
64-bit coefficients z_i (z_0 = 1),

    prod_i e(z_i * aggpk_i, H(m_i)) * e(-g1, sum_i z_i * sig_i) == 1

A backend must implement `verify_signature_sets(sets, rands)` and may expose
accelerated primitives. The "fake" backend validates nothing — it proves the
batch plumbing, like /root/reference/crypto/bls/src/impls/fake_crypto.rs.
"""

from __future__ import annotations

import os
import secrets
from typing import Callable, Sequence

from ..bls381 import curve as cv
from ..bls381 import pairing as pr
from ..bls381 import hash_to_curve as h2c
from ..bls381.constants import DST_POP
from .keys import PublicKey, SecretKey
from .signature import AggregateSignature, Signature
from .signature_set import SignatureSet

RANDOM_BITS = 64


def _default_rands(n: int) -> list[int]:
    # z_0 may be 1 (blst does this too); all must be nonzero.
    return [1] + [secrets.randbits(RANDOM_BITS) | 1 for _ in range(n - 1)] if n else []


def hash_to_g2_point(message: bytes):
    return h2c.hash_to_g2(message, DST_POP)


# ----------------------------------------------------------------- backends


class _ReadyHandle:
    """Immediate-resolution handle for backends without async submission."""

    __slots__ = ("_value",)

    def __init__(self, value: bool):
        self._value = value

    def result(self) -> bool:
        return self._value


class PythonBackend:
    """Pure-Python ground-truth backend."""

    name = "python"

    def verify_signature_sets(self, sets: Sequence[SignatureSet], rands: Sequence[int]) -> bool:
        pairs = []
        sig_acc = None
        for s, z in zip(sets, rands):
            agg_pk = None
            for pk in s.signing_keys:
                agg_pk = cv.g1_add(agg_pk, pk.point)
            if agg_pk is None:
                return False
            msg_pt = hash_to_g2_point(s.message)
            pairs.append((cv.g1_mul(agg_pk, z), msg_pt))
            sig_acc = cv.g2_add(sig_acc, cv.g2_mul(s.signature.point, z) if s.signature.point else None)
        pairs.append((cv.g1_neg(cv.G1_GEN), sig_acc))
        return pr.multi_pairing_is_one(pairs)

    def verify_single(self, pk: PublicKey, message: bytes, sig: Signature) -> bool:
        if sig.is_infinity():
            return False
        msg_pt = hash_to_g2_point(message)
        return pr.multi_pairing_is_one([(pk.point, msg_pt), (cv.g1_neg(cv.G1_GEN), sig.point)])

    def aggregate_verify(self, pks: Sequence[PublicKey], messages: Sequence[bytes], sig: Signature) -> bool:
        pairs = [(pk.point, hash_to_g2_point(m)) for pk, m in zip(pks, messages)]
        pairs.append((cv.g1_neg(cv.G1_GEN), sig.point))
        return pr.multi_pairing_is_one(pairs)


    def verify_kzg_batch_async(self, commitments, proofs, r_pows,
                               y_scalars, z_scalars, tau_g2):
        """The group side of `verify_blob_kzg_proof_batch` in Python
        integers, resolved at once (the entry `crypto/kzg.py` submits blob
        batches through on every backend; the jax backend's form is the
        served one): every point times the group order, then
        e(C', H) e(-W', tau H) == 1. Returns a handle whose `.result()` is
        (ok, [(C_i in the subgroup, W_i in it), ...])."""
        flags = [
            (c is None or cv.g1_in_subgroup(c), w is None or cv.g1_in_subgroup(w))
            for c, w in zip(commitments, proofs)
        ]
        if not all(c and w for c, w in flags):
            return _ReadyHandle((False, flags))
        c_prime = w_prime = None
        for c, w, r, ys, zs in zip(commitments, proofs, r_pows, y_scalars,
                                   z_scalars):
            for point, scalar in ((c, r), (cv.G1_GEN, ys), (w, zs)):
                if point is not None:
                    c_prime = cv.g1_add(c_prime, cv.g1_mul(point, scalar))
            if w is not None:
                w_prime = cv.g1_add(w_prime, cv.g1_mul(w, r))
        pairs = [(c_prime, cv.G2_GEN), (cv.g1_neg(w_prime), tau_g2)]
        ok = pr.multi_pairing_is_one([p for p in pairs if p[0] is not None])
        return _ReadyHandle((ok, flags))


class FakeBackend:
    """Always-valid stub (plumbing tests only). Like the reference's
    fake_crypto.rs it also no-ops SIGNING: `sign()` returns a fixed valid
    G2 point, so plumbing lanes that sign through production code paths
    (validator stores, the fleet harness) skip the ~50ms hash-to-curve +
    scalar mul per message."""

    name = "fake"
    _sig_cache: "Signature | None" = None

    def verify_signature_sets(self, sets, rands) -> bool:
        return all(len(s.signing_keys) > 0 for s in sets)

    def verify_single(self, pk, message, sig) -> bool:
        return True

    def aggregate_verify(self, pks, messages, sig) -> bool:
        return True

    def verify_kzg_batch_async(self, commitments, proofs, *_scalars):
        return _ReadyHandle((True, [(True, True)] * len(commitments)))

    def sign(self, sk: SecretKey, message: bytes) -> Signature:
        if FakeBackend._sig_cache is None:
            FakeBackend._sig_cache = Signature(cv.G2_GEN)
        return FakeBackend._sig_cache


_BACKENDS: dict[str, object] = {}
_active_backend = None


def register_backend(name: str, backend) -> None:
    _BACKENDS[name] = backend


register_backend("python", PythonBackend())
register_backend("fake", FakeBackend())


def _load_jax_backend():
    try:
        from ..jaxbls.backend import JaxBackend  # deferred: importing jax is slow
    except ImportError as e:
        raise ValueError(f"jax BLS backend unavailable: {e}") from e
    backend = JaxBackend()
    register_backend("jax", backend)
    return backend


def _load_hybrid_backend():
    """Host/device routing policy (crypto/bls/hybrid.py): urgent or tiny
    verifies ride the host path while the device is cold, absent, or over
    its latency budget — the serving story for a node started during a
    device outage (SURVEY §7 hard part (d))."""
    from .hybrid import HybridBackend

    backend = HybridBackend()
    register_backend("hybrid", backend)
    return backend


def available_backends() -> list[str]:
    return sorted(set(_BACKENDS) | {"jax", "hybrid"})


def set_backend(name: str):
    global _active_backend
    if name == "jax" and "jax" not in _BACKENDS:
        _load_jax_backend()
    if name == "hybrid" and "hybrid" not in _BACKENDS:
        _load_hybrid_backend()
    if name not in _BACKENDS:
        raise ValueError(f"unknown BLS backend {name!r}; have {available_backends()}")
    _active_backend = _BACKENDS[name]
    return _active_backend


def get_backend():
    global _active_backend
    if _active_backend is None:
        set_backend(os.environ.get("LIGHTHOUSE_TPU_BLS_BACKEND", "python"))
    return _active_backend


# ----------------------------------------------------------------- operations


def sign(sk: SecretKey, message: bytes) -> Signature:
    backend_sign = getattr(get_backend(), "sign", None)
    if backend_sign is not None:
        return backend_sign(sk, message)
    return Signature(cv.g2_mul(hash_to_g2_point(message), sk.scalar))


def verify(pk: PublicKey, message: bytes, signature: Signature) -> bool:
    return get_backend().verify_single(pk, message, signature)


def aggregate_verify(pks: Sequence[PublicKey], messages: Sequence[bytes], signature: Signature) -> bool:
    """Distinct-message aggregate verification (IETF AggregateVerify)."""
    if len(pks) != len(messages) or not pks:
        return False
    if signature.is_infinity():
        return False
    return get_backend().aggregate_verify(pks, messages, signature)


def fast_aggregate_verify(pks: Sequence[PublicKey], message: bytes, signature: Signature) -> bool:
    """Same-message aggregate verification (IETF FastAggregateVerify)."""
    if not pks:
        return False
    s = SignatureSet(signature, pks, message)
    return verify_signature_sets([s])


def eth_fast_aggregate_verify(pks: Sequence[PublicKey], message: bytes, signature: Signature) -> bool:
    """Spec variant: empty pubkeys + infinity signature is valid
    (used for empty sync aggregates)."""
    if not pks and signature.is_infinity():
        return True
    return fast_aggregate_verify(pks, message, signature)


def verify_signature_sets(
    sets: Sequence[SignatureSet],
    rand_fn: Callable[[int], Sequence[int]] | None = None,
) -> bool:
    """Verify a batch of signature sets with one combined pairing check.

    `rand_fn(n)` supplies the n random coefficients — a determinism seam for
    tests and for host/device coefficient agreement (SURVEY §7 hard part (e)).

    Matching blst semantics (/root/reference/crypto/bls/src/impls/blst.rs:40):
    an empty batch and any infinity signature are deterministic failures.
    """
    sets = list(sets)
    if not sets:
        return False
    if any(s.signature.is_infinity() for s in sets):
        return False
    rands = (rand_fn or _default_rands)(len(sets))
    if len(rands) != len(sets):
        raise ValueError("rand_fn returned wrong number of coefficients")
    from ..bls381.constants import R as _R

    if any(z % _R == 0 for z in rands):
        raise ValueError("batch verification coefficients must be nonzero")
    return get_backend().verify_signature_sets(sets, rands)


def verify_signature_sets_async(
    sets: Sequence[SignatureSet],
    rand_fn: Callable[[int], Sequence[int]] | None = None,
):
    """Submit a batch for verification; returns a handle whose .result()
    blocks. On the TPU backend this keeps the device busy while the host
    marshals the next batch (the double-buffered dispatch of SURVEY §7
    step 2); other backends resolve immediately."""
    sets = list(sets)
    if not sets or any(s.signature.is_infinity() for s in sets):
        return _ReadyHandle(False)
    rands = (rand_fn or _default_rands)(len(sets))
    if len(rands) != len(sets):
        raise ValueError("rand_fn returned wrong number of coefficients")
    from ..bls381.constants import R as _R

    if any(z % _R == 0 for z in rands):
        raise ValueError("batch verification coefficients must be nonzero")
    backend = get_backend()
    submit = getattr(backend, "verify_signature_sets_async", None)
    if submit is None:
        return _ReadyHandle(backend.verify_signature_sets(sets, rands))
    return submit(sets, rands)
