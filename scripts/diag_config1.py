"""Reproduce + bisect the bench-config-1 on-chip failure, exactly.

The (4, 2)-shaped stage bisect (diag_small_bucket.py) is bit-identical
CPU-vs-TPU, yet bench configs 1/3 — a single REAL fixture set padded to the
n=4 bucket with m=128/512 pubkeys — return False on the chip. This driver
replays config 1 verbatim (same fixture set, same rands=[1], same backend
call), and on failure re-runs the staged pipeline capturing every boundary,
comparing against EXACT host-integer references computed with the
pure-python bls381 layer (pairing there is ~60ms — no CPU-JAX compiles).

Run on the TPU:  python scripts/diag_config1.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from lighthouse_tpu.utils.jaxcfg import setup_compilation_cache

setup_compilation_cache()

import numpy as np
import jax

os.chdir(os.path.join(os.path.dirname(__file__), ".."))

from bench import _load_fixtures
import lighthouse_tpu.crypto.jaxbls.backend as be
from lighthouse_tpu.crypto.jaxbls import limbs as lb
from lighthouse_tpu.crypto.jaxbls import h2c_ops as h2
from lighthouse_tpu.crypto.bls381 import curve as pc
from lighthouse_tpu.crypto.bls381 import hash_to_curve as ph2c
from lighthouse_tpu.crypto.bls import api as bls_api


def main():
    print(f"devices: {jax.devices()}", flush=True)
    fx = _load_fixtures()
    s = fx["att"][0]
    backend = bls_api.set_backend("jax")

    t0 = time.time()
    got = backend.verify_signature_sets([s], [1])
    print(f"config-1 verbatim verify: {got} ({time.time()-t0:.1f}s)", flush=True)

    # independent host check of the same set (exact integer pipeline)
    pkpts = [pk.point for pk in s.signing_keys]
    agg = None
    for p in pkpts:
        agg = pc.g1_add(agg, p) if agg else p
    hpt = ph2c.hash_to_g2(s.message, backend.dst)
    from lighthouse_tpu.crypto.bls381 import pairing as pp

    host_ok = pp.multi_pairing_is_one(
        [(agg, hpt), (pc.g1_neg(pc.G1_GEN), s.signature.point)]
    )
    print(f"host pure-python verify of the same set: {host_ok}", flush=True)

    if got and host_ok:
        print("NO REPRODUCTION — device agrees with host", flush=True)
        return 0

    # ---- stage bisect at the same bucket the real path uses ----
    # padding_bucket is verify_signature_sets' own bucket math;
    # NOTE on a multi-device VM the real path additionally mesh-shards its
    # inputs (parallel.put_sets) — this bisect runs unsharded, so a
    # mesh-layout-specific divergence can reproduce verbatim but not here.
    n, m = be.padding_bucket(1, len(s.signing_keys))
    print(f"bisecting at bucket n={n} m={m}", flush=True)
    pk_x, pk_y, pk_mask = backend._marshal_pubkeys([s], be.one_key_grid(n, m))
    sig_x = np.zeros((n, 2, lb.NL), np.uint32)
    sig_y = np.zeros((n, 2, lb.NL), np.uint32)
    z_digits = np.zeros((n, be.Z_BITS), np.uint32)
    set_mask = np.zeros((n,), np.uint32)
    sp = s.signature.point
    sig_x[0, 0] = lb.pack(sp[0][0])
    sig_x[0, 1] = lb.pack(sp[0][1])
    sig_y[0, 0] = lb.pack(sp[1][0])
    sig_y[0, 1] = lb.pack(sp[1][1])
    z_digits[0, be.Z_BITS - 1] = 1          # z = 1, MSB-first bits
    set_mask[0] = 1
    us = np.zeros((n, 2, 2, lb.NL), np.uint32)
    us[:1] = h2.hash_to_field_batch([s.message], backend.dst)

    prepare, h2c_stage, pairs_stage, pairing_stage = be._get_stages()
    z_pk, sig_acc, bad = prepare(pk_x, pk_y, pk_mask, sig_x, sig_y,
                                 jax.numpy.asarray(z_digits),
                                 jax.numpy.asarray(set_mask))
    h_jac = h2c_stage(jax.numpy.asarray(us))
    px, py, qxx, qyy, pair_mask = pairs_stage(z_pk, h_jac, sig_acc,
                                              jax.numpy.asarray(set_mask))
    ok = pairing_stage(px, py, qxx, qyy, pair_mask)
    print(f"staged: ok={bool(np.asarray(ok))} bad={bool(np.asarray(bad))} "
          f"pair_mask={np.asarray(pair_mask)}", flush=True)

    def aff_int(xm, ym):
        return (lb.unpack(np.asarray(jax.jit(lb.from_mont)(xm))),
                lb.unpack(np.asarray(jax.jit(lb.from_mont)(ym))))

    # pair 0: (1 * aggpk, H(msg))
    got_p0 = aff_int(px[0], py[0])
    print(f"pair0 G1 matches host aggpk: {got_p0 == agg}", flush=True)
    got_q0x = (lb.unpack(np.asarray(jax.jit(lb.from_mont)(qxx[0, 0]))),
               lb.unpack(np.asarray(jax.jit(lb.from_mont)(qxx[0, 1]))))
    got_q0y = (lb.unpack(np.asarray(jax.jit(lb.from_mont)(qyy[0, 0]))),
               lb.unpack(np.asarray(jax.jit(lb.from_mont)(qyy[0, 1]))))
    print(f"pair0 G2 matches host H(msg): {(got_q0x, got_q0y) == (hpt[0], hpt[1])}",
          flush=True)

    # final pair: (-G1gen, sig_acc) with sig_acc == 1 * sig
    got_p4 = aff_int(px[n], py[n])
    ng = pc.g1_neg(pc.G1_GEN)
    print(f"sig-pair G1 is -G1gen: {got_p4 == ng}", flush=True)
    got_q4x = (lb.unpack(np.asarray(jax.jit(lb.from_mont)(qxx[n, 0]))),
               lb.unpack(np.asarray(jax.jit(lb.from_mont)(qxx[n, 1]))))
    got_q4y = (lb.unpack(np.asarray(jax.jit(lb.from_mont)(qyy[n, 0]))),
               lb.unpack(np.asarray(jax.jit(lb.from_mont)(qyy[n, 1]))))
    print(f"sig-pair G2 is the signature: {(got_q4x, got_q4y) == (sp[0], sp[1])}",
          flush=True)
    want_mask = [True] + [False] * (n - 1) + [True]
    print(f"pair_mask expected {want_mask} got {list(np.asarray(pair_mask) != 0)}",
          flush=True)
    return 1


if __name__ == "__main__":
    sys.exit(main())
