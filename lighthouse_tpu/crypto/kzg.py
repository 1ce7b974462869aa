"""KZG commitments / blob proofs (EIP-4844) on the shared BLS12-381 core.

Parity surface: /root/reference/crypto/kzg (c-kzg wrapper): trusted-setup
loading, blob_to_kzg_commitment, compute/verify_blob_kzg_proof and the
batch verifier (src/lib.rs:47-81). The pairing / G1 arithmetic is the SAME
code path the BLS backend uses (bls381 + jaxbls) — the north star's
"blob proofs reuse the pairing kernel" (BASELINE.json).

Verification is ONE path on every backend (`BlobBatch`): the host does what
is bytes and Fr — lengths, canonical field elements, decompression to (x, y)
without the subgroup check, the Fiat-Shamir challenges, the barycentric
evaluations (`native/fr_blob.cc`, built on first use; the same sum in Python
integers where no compiler is at hand), the r-powers — and hands the group
side to the active BLS
backend's `verify_kzg_batch_async`: every commitment and proof times the
group order (the subgroup checks), the spec's two linear combinations and
the two-pair check. On the jax backend that is one pipelined dispatch on the
device ledger's `kzg` tenant with one device read (crypto/jaxbls/backend.py,
msm.kzg_lincomb_kernel); the python backend resolves it in integers. Nothing
of a request — verdict, challenge, evaluation, validated point — outlives it.
Committing and proving (`_g1_lincomb`) reach the backend's MSMs where it has
them (the jax backend's `g1_msm`, and `g1_msm_fixed` over the setup's
Lagrange points) and sum on the host otherwise.

Trusted setup: the production ceremony file (JSON with g1_lagrange /
g2_monomial points) loads via `TrustedSetup.from_json`. For tests,
`TrustedSetup.insecure_dev_setup(n)` derives one from a known tau — NEVER
for production (tau is public!).
"""

from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass, field
from ..observability import trace as _obs
from ..utils.metrics import REGISTRY
from .bls381 import curve as cv
from .bls381 import serde
from .bls381.constants import R

_HOST_SECONDS = REGISTRY.histogram_vec(
    "kzg_host_seconds",
    "host seconds of one blob batch's preparation, by part: field = blobs "
    "to canonical field elements, challenges, barycentric evaluations, "
    "r-powers; points = decompression of commitments and proofs to (x, y) "
    "on the curve (their subgroup checks run where the batch's scalar "
    "multiplications run)",
    ("part",),
    buckets=(0.0005, 0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
             5.0),
)
_BLOBS_EVALUATED = REGISTRY.counter(
    "kzg_blobs_evaluated_total",
    "blobs whose polynomial was evaluated at its challenge for a batch",
)
_POINTS_VALIDATED = REGISTRY.counter(
    "kzg_points_validated_total",
    "commitments and proofs decompressed on the host and submitted to the "
    "backend's subgroup check, two a blob of a batch",
)

BYTES_PER_FIELD_ELEMENT = 32
FIAT_SHAMIR_PROTOCOL_DOMAIN = b"FSBLOBVERIFY_V1_"
RANDOM_CHALLENGE_DOMAIN = b"RCKZGBATCH___V1_"

# Fr primitive root of unity for power-of-two subgroups: 7 is a generator
# of Fr*; omega_n = 7^((r-1)/n).
_FR_GENERATOR = 7


class KzgError(Exception):
    pass


def _fr_roots_of_unity(n: int) -> list[int]:
    assert (R - 1) % n == 0
    omega = pow(_FR_GENERATOR, (R - 1) // n, R)
    roots = [1] * n
    for i in range(1, n):
        roots[i] = roots[i - 1] * omega % R
    # bit-reversal permutation (c-kzg stores roots bit-reversed)
    bits = (n - 1).bit_length()
    return [roots[int(format(i, f"0{bits}b")[::-1], 2)] for i in range(n)]


@dataclass
class TrustedSetup:
    g1_lagrange: list          # n G1 affine points (bit-reversed order)
    g2_monomial: list          # >=2 G2 affine points: [H, tau*H, ...]
    roots: list                # n roots of unity, bit-reversed
    # root -> its index, built once: an evaluation at a point of the domain
    # is a lookup, not a scan of the roots
    root_index: dict = field(init=False, repr=False, compare=False)
    # the roots as the native evaluation reads them: n x 32 bytes, big-endian
    roots_bytes: bytes = field(init=False, repr=False, compare=False)

    def __post_init__(self):
        self.root_index = {w: i for i, w in enumerate(self.roots)}
        self.roots_bytes = b"".join(w.to_bytes(32, "big") for w in self.roots)

    @property
    def n(self) -> int:
        return len(self.g1_lagrange)

    @classmethod
    def from_json(cls, text: str) -> "TrustedSetup":
        data = json.loads(text)
        g1 = [serde.g1_decompress(bytes.fromhex(p.removeprefix("0x")))
              for p in data["g1_lagrange"]]
        g2 = [serde.g2_decompress(bytes.fromhex(p.removeprefix("0x")))
              for p in data["g2_monomial"]]
        return cls(g1_lagrange=g1, g2_monomial=g2, roots=_fr_roots_of_unity(len(g1)))

    @classmethod
    def insecure_dev_setup(cls, n: int = 64) -> "TrustedSetup":
        """Deterministic setup from a KNOWN tau — testing only."""
        lis, tau = cls.dev_setup_scalars(n)
        g1 = [cv.g1_mul(cv.G1_GEN, li) for li in lis]
        g2 = [cv.G2_GEN, cv.g2_mul(cv.G2_GEN, tau)]
        return cls(g1_lagrange=g1, g2_monomial=g2, roots=_fr_roots_of_unity(n))

    @classmethod
    def dev_verifier_setup(cls, n: int = 4096) -> "TrustedSetup":
        """The verifier's half of the insecure dev setup: [tau]G2 and the n
        roots of unity, which is all verification reads — one G2
        multiplication instead of n G1 ones. Its Lagrange points are
        placeholders: it cannot commit or prove. Testing and benchmarks
        only (tau is public)."""
        _lis, tau = cls.dev_setup_scalars(1)
        g2 = [cv.G2_GEN, cv.g2_mul(cv.G2_GEN, tau)]
        return cls(g1_lagrange=[None] * n, g2_monomial=g2, roots=_fr_roots_of_unity(n))

    @classmethod
    def dev_setup_scalars(cls, n: int) -> tuple[list[int], int]:
        """(lagrange-basis scalars at tau, tau) for the insecure dev setup —
        lets callers with a batched device scalar-mul (bench.py) build the
        big setup without n host point multiplications.
        L_i(tau) = (tau^n - 1) * w_i / (n * (tau - w_i)) over the
        bit-reversed domain. NEVER for production (tau is public)."""
        tau = int.from_bytes(hashlib.sha256(b"lighthouse-tpu-dev-tau").digest(), "big") % R
        roots = _fr_roots_of_unity(n)
        tau_n = pow(tau, n, R)
        denom_invs = _fr_batch_inverse([n * (tau - w) % R for w in roots])
        return [(tau_n - 1) * w % R * dinv % R for w, dinv in zip(roots, denom_invs)], tau


# ------------------------------------------------------------ blob handling


def blob_to_polynomial(blob: bytes, setup: TrustedSetup) -> list[int]:
    n = setup.n
    if len(blob) != n * BYTES_PER_FIELD_ELEMENT:
        raise KzgError(f"blob must be {n*32} bytes")
    out = []
    for i in range(n):
        fe = int.from_bytes(blob[i * 32 : (i + 1) * 32], "big")
        if fe >= R:
            raise KzgError("blob field element out of range")
        out.append(fe)
    return out


def _fr_batch_inverse(xs: list[int]) -> list[int]:
    """Montgomery batch inversion: ONE field exponentiation + 3(n-1)
    multiplications for n inverses (vs n exponentiations) — the same trick
    c-kzg uses, for the quotient polynomial and the dev setup (the
    evaluation itself needs no inverse). Zero entries map to zero."""
    n = len(xs)
    prefix = [1] * (n + 1)
    for i, x in enumerate(xs):
        prefix[i + 1] = prefix[i] * (x if x % R else 1) % R
    inv_all = pow(prefix[n], R - 2, R)
    out = [0] * n
    for i in range(n - 1, -1, -1):
        x = xs[i] % R
        if x:
            out[i] = inv_all * prefix[i] % R
            inv_all = inv_all * x % R
    return out


def _evaluate_polynomial_in_evaluation_form(poly: list[int], z: int, setup: TrustedSetup) -> int:
    """Barycentric evaluation over the bit-reversed domain:
    p(z) = (z^n - 1)/n * sum_i p_i w_i / (z - w_i). The product of all
    (z - w_i) IS z^n - 1, so with the sum kept as one fraction N / D
    (N <- N b_i + p_i w_i D, D <- D b_i, b_i = z - w_i) the answer is N / n:
    no inversion but the constant's (`native/fr_blob.cc` is this loop)."""
    on_domain = setup.root_index.get(z)
    if on_domain is not None:
        return poly[on_domain]
    num, den = 0, 1
    for p_i, w in zip(poly, setup.roots):
        b = z - w
        num = (num * b + p_i * w * den) % R
        den = den * b % R
    return num * pow(setup.n, -1, R) % R


_fr_native = None
_fr_native_tried = False


def _load_fr_native():
    """Build/load native/fr_blob.cc; the ctypes lib, or None (logged once:
    the evaluations then run in Python integers, ~6 ms a blob for ~1)."""
    global _fr_native, _fr_native_tried
    if _fr_native_tried:
        return _fr_native
    _fr_native_tried = True
    try:
        import ctypes
        from pathlib import Path

        from ..utils.native_build import build_native

        lib = ctypes.CDLL(str(build_native(
            Path(__file__).parent / "native" / "fr_blob.cc", "libltfr.so"
        )))
        lib.fr_blob_evaluate.restype = ctypes.c_int
        lib.fr_blob_evaluate.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_char_p,
        ]
        _fr_native = lib
    except Exception as e:
        from ..utils.logging import get_logger

        get_logger("kzg").warn(
            "native Fr evaluation unavailable; evaluating blobs in Python",
            error=f"{type(e).__name__}: {e}",
        )
    return _fr_native


def _evaluate_blob(blob: bytes, z: int, setup: TrustedSetup) -> int:
    """p(z) for the polynomial a blob is, every field element of it checked
    canonical on the way (KzgError otherwise, as `blob_to_polynomial`)."""
    lib = _load_fr_native()
    if lib is None:
        return _evaluate_polynomial_in_evaluation_form(
            blob_to_polynomial(blob, setup), z, setup
        )
    import ctypes

    if len(blob) != setup.n * BYTES_PER_FIELD_ELEMENT:
        raise KzgError(f"blob must be {setup.n * 32} bytes")
    y = ctypes.create_string_buffer(BYTES_PER_FIELD_ELEMENT)
    if lib.fr_blob_evaluate(blob, setup.n, setup.roots_bytes,
                            z.to_bytes(32, "big"), y):
        raise KzgError("blob field element out of range")
    return int.from_bytes(y.raw, "big")


def _compute_quotient_eval_form(poly, z: int, y: int, setup: TrustedSetup) -> list[int]:
    """q_i = (p_i - y) / (w_i - z) on the domain (z not in domain assumed
    handled by caller special-case)."""
    n = setup.n
    q = [0] * n
    inverses = _fr_batch_inverse([(w - z) % R for w in setup.roots])
    special = setup.root_index.get(z)
    if special is None:
        for i in range(n):
            q[i] = (poly[i] - y) * inverses[i] % R
        return q
    # z on domain: classic c-kzg special-case
    for i in range(n):
        if i == special:
            continue
        q[i] = (poly[i] - y) * inverses[i] % R
    acc = 0
    wz = setup.roots[special]
    denom_invs = _fr_batch_inverse([(wz - w) % R * wz % R for w in setup.roots])
    for i in range(n):
        if i == special:
            continue
        w = setup.roots[i]
        term = (poly[i] - y) * w % R * denom_invs[i] % R
        acc = (acc + term) % R
    q[special] = acc
    return q


def _g1_lincomb(points, scalars, fixed_base: bool = False) -> object:
    """MSM sum(scalars[i] * points[i]); dispatches to the active BLS backend
    if it exposes an accelerated MSM, else host-side.

    fixed_base=True marks a STABLE point set (the setup's Lagrange basis —
    identical list object every call): the backend may then build and cache
    per-point comb tables (jaxbls/msm.py). Never set it for per-call
    varying points — the one-time table build would be paid every call."""
    from .bls import api as bls_api

    backend = bls_api.get_backend()
    if fixed_base and len(points) >= 256:
        msm_fixed = getattr(backend, "g1_msm_fixed", None)
        if msm_fixed is not None:
            return msm_fixed(points, scalars)
    msm = getattr(backend, "g1_msm", None)
    if msm is not None:
        return msm(points, scalars)
    acc = None
    for pt, s in zip(points, scalars):
        if s == 0 or pt is None:
            continue
        acc = cv.g1_add(acc, cv.g1_mul(pt, s))
    return acc


# ------------------------------------------------------------ public API


def blob_to_kzg_commitment(blob: bytes, setup: TrustedSetup):
    poly = blob_to_polynomial(blob, setup)
    return _g1_lincomb(setup.g1_lagrange, poly, fixed_base=True)


def _hash_to_bls_field(data: bytes) -> int:
    return int.from_bytes(hashlib.sha256(data).digest(), "big") % R


def compute_challenge(blob: bytes, commitment_bytes: bytes, setup: TrustedSetup) -> int:
    """Deneb compute_challenge: domain || degree_poly (16-byte big-endian
    FIELD_ELEMENTS_PER_BLOB) || blob || commitment. With a production 4096-
    element setup this transcript is byte-identical to c-kzg's."""
    degree = setup.n.to_bytes(16, "big")
    return _hash_to_bls_field(FIAT_SHAMIR_PROTOCOL_DOMAIN + degree + blob + commitment_bytes)


def compute_kzg_proof(blob: bytes, z: int, setup: TrustedSetup):
    """Returns (proof_point, y)."""
    poly = blob_to_polynomial(blob, setup)
    y = _evaluate_polynomial_in_evaluation_form(poly, z, setup)
    q = _compute_quotient_eval_form(poly, z, y, setup)
    return _g1_lincomb(setup.g1_lagrange, q, fixed_base=True), y


def compute_blob_kzg_proof(blob: bytes, commitment_bytes: bytes, setup: TrustedSetup):
    z = compute_challenge(blob, commitment_bytes, setup)
    proof, _y = compute_kzg_proof(blob, z, setup)
    return proof


# ------------------------------------------------------------ verification

#: blobs one backend submission holds (the jax backend's lane pass has 16
#: blob slots, msm.KZG_BLOB_SLOTS: mainnet's MAX_BLOBS_PER_BLOCK is 6,
#: Electra's 9)
MAX_BATCH = 16


def _submit_points(commitments, proofs, zs, ys, r_pows, setup: TrustedSetup):
    """The group side of a batch through the active backend's one entry:
    e(sum r^i (C_i - y_i G1 + z_i W_i), H) == e(sum r^i W_i, tau H), every
    C_i and W_i checked for the subgroup on the way. Returns the backend's
    handle: `.result()` -> (ok, [(C_i in the subgroup, W_i in it), ...])."""
    from .bls import api as bls_api

    return bls_api.get_backend().verify_kzg_batch_async(
        commitments, proofs, r_pows,
        [(-y * r) % R for y, r in zip(ys, r_pows)],
        [z * r % R for z, r in zip(zs, r_pows)],
        setup.g2_monomial[1],
    )


def verify_kzg_proof(commitment, z: int, y: int, proof, setup: TrustedSetup) -> bool:
    """e(P - y*G1, H) == e(W, tau*H - z*H)  <=>
       e(P - y*G1 + z*W, H) * e(-W, tau*H) == 1: the batch check of one."""
    ok, flags = _submit_points(
        [commitment], [proof], [z % R], [y % R], [1], setup
    ).result()
    return ok and all(flags[0])


def compute_r_powers(commitments_bytes, zs, ys, proofs_bytes, setup: TrustedSetup) -> list[int]:
    """Deneb compute_r_powers: domain || degree_poly (8-byte BE) ||
    num_blobs (8-byte BE) || per-blob (commitment || z || y || proof)."""
    n = len(commitments_bytes)
    transcript = RANDOM_CHALLENGE_DOMAIN + setup.n.to_bytes(8, "big") + n.to_bytes(8, "big")
    for cb, z, y, pb in zip(commitments_bytes, zs, ys, proofs_bytes):
        transcript += cb + z.to_bytes(32, "big") + y.to_bytes(32, "big") + pb
    r = _hash_to_bls_field(transcript)
    r_pows = [1] * n
    for i in range(1, n):
        r_pows[i] = r_pows[i - 1] * r % R
    return r_pows


class BlobBatch:
    """One `verify_blob_kzg_proof_batch` over untrusted bytes, in two halves
    so a caller can leave the device to it meanwhile: the constructor does
    the host's part, `submit()` hands the group side to the backend and
    returns its handle, `verdicts(handle.result())` reads the answer.

    A sidecar whose bytes are malformed — a wrong length, a field element
    >= r, a commitment or proof that is no compressed point of the curve —
    is False here (`malformed`) and never joins the batch; the r-powers
    are drawn over the members alone. Membership of G1's subgroup is
    checked by the backend, beside the scalar multiplications."""

    def __init__(self, blobs, commitments_bytes, proofs_bytes, setup: TrustedSetup):
        n = len(blobs)
        if not (n == len(commitments_bytes) == len(proofs_bytes)):
            raise KzgError("length mismatch")
        if n > MAX_BATCH:
            raise KzgError(f"a batch holds at most {MAX_BATCH} blobs")
        self.setup = setup
        self.n = n
        self.malformed = [False] * n
        self.members: list[int] = []        # indices that form the batch
        self._commitments, self._proofs = [], []
        self._zs, self._ys = [], []
        member_cbs, member_pbs = [], []
        blobs = [bytes(b) for b in blobs]
        cbs = [bytes(c) for c in commitments_bytes]
        pbs = [bytes(p) for p in proofs_bytes]
        points: list = [None] * n
        with _obs.span("kzg:points", blobs=n) as sp:
            for i in range(n):
                try:
                    points[i] = (
                        serde.g1_decompress(cbs[i], subgroup_check=False),
                        serde.g1_decompress(pbs[i], subgroup_check=False),
                    )
                except serde.DecodeError:
                    self.malformed[i] = True
        _HOST_SECONDS.labels("points").observe(sp.t1 - sp.t0)
        with _obs.span("kzg:field", blobs=n) as sp:
            for i in range(n):
                if self.malformed[i]:
                    continue
                z = compute_challenge(blobs[i], cbs[i], setup)
                try:
                    self._ys.append(_evaluate_blob(blobs[i], z, setup))
                except KzgError:
                    self.malformed[i] = True
                    continue
                self._zs.append(z)
                self._commitments.append(points[i][0])
                self._proofs.append(points[i][1])
                self.members.append(i)
                member_cbs.append(cbs[i])
                member_pbs.append(pbs[i])
            self._r_pows = compute_r_powers(member_cbs, self._zs, self._ys,
                                            member_pbs, setup)
        _HOST_SECONDS.labels("field").observe(sp.t1 - sp.t0)
        _BLOBS_EVALUATED.inc(len(self.members))

    def submit(self):
        """Hand the members' group side to the backend. Returns a handle
        with `.result()` (resolved already where no member is left)."""
        if not self.members:
            from .bls import api as bls_api

            return bls_api._ReadyHandle((True, []))
        _POINTS_VALIDATED.inc(2 * len(self.members))
        return _submit_points(self._commitments, self._proofs, self._zs,
                              self._ys, self._r_pows, self.setup)

    def verdicts(self, result) -> list:
        """One entry a sidecar from `submit().result()`: False = malformed,
        or its commitment or proof lies outside the subgroup; True = the
        batch verified and every point of it is valid; None = undecided
        (the batch came back False, or a bad point spoiled its sums): that
        sidecar has to be verified alone (`verify_blob_kzg_proof`). A batch
        of one is its own single verification and always decided."""
        ok, flags = result
        out: list = [False if bad else None for bad in self.malformed]
        clean = all(c and w for c, w in flags)
        for i, (c, w) in zip(self.members, flags):
            if not (c and w):
                out[i] = False
            elif clean and (ok or len(self.members) == 1):
                out[i] = ok
        return out

    def all_valid(self, result) -> bool:
        """The batch as the spec's one boolean."""
        ok, flags = result
        return (not any(self.malformed) and ok
                and all(c and w for c, w in flags))


def verify_blob_kzg_proof(blob: bytes, commitment_bytes: bytes, proof_bytes: bytes, setup: TrustedSetup) -> bool:
    batch = BlobBatch([blob], [commitment_bytes], [proof_bytes], setup)
    return batch.all_valid(batch.submit().result())


def verify_blob_kzg_proof_batch(blobs, commitments_bytes, proofs_bytes, setup: TrustedSetup) -> bool:
    """Batch verification with a random linear combination collapsing all
    blobs into ONE two-pairing check (crypto/kzg verify_blob_kzg_proof_batch
    analog — and the same shape the TPU pairing kernel consumes). Malformed
    input is False, as an assertion of the spec's is an invalid block; more
    than MAX_BATCH blobs are verified MAX_BATCH at a time."""
    n = len(blobs)
    if not (n == len(commitments_bytes) == len(proofs_bytes)):
        raise KzgError("length mismatch")
    for k in range(0, n, MAX_BATCH):
        batch = BlobBatch(blobs[k:k + MAX_BATCH], commitments_bytes[k:k + MAX_BATCH],
                          proofs_bytes[k:k + MAX_BATCH], setup)
        if not batch.all_valid(batch.submit().result()):
            return False
    return True
