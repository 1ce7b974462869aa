"""Bisect the small-bucket (n=MIN_SETS=4) verify failure across devices.

Bench configs 1/3 (single-set verifies padded to the 4-set bucket) return
False for KNOWN VALID sets on the real TPU while the identical code is green
on CPU and the 131-set config-2 batch is green on BOTH. This tool runs the
staged verify pipeline once per platform on IDENTICAL deterministic inputs
(the driver entry's n=4 fixture) and dumps every stage boundary, so a single
compare run pinpoints the first tensor that diverges.

Usage:
  JAX_PLATFORMS=cpu python scripts/diag_small_bucket.py save /tmp/sb_cpu.npz
  python scripts/diag_small_bucket.py compare /tmp/sb_cpu.npz   # on the TPU
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def run_stages():
    from lighthouse_tpu.utils.jaxcfg import setup_compilation_cache

    setup_compilation_cache()
    import jax
    import numpy as np

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")

    from __graft_entry__ import _example_inputs
    from lighthouse_tpu.crypto.jaxbls import backend as be
    from lighthouse_tpu.crypto.jaxbls import h2c_ops as h2

    be._init_consts()
    pk_x, pk_y, pk_mask, sig_x, sig_y, us, z_digits, set_mask = _example_inputs(
        n_sets=4, n_pks=2
    )
    print(f"platform: {jax.default_backend()} {jax.devices()}", flush=True)

    out = {}
    z_pk, sig_acc, bad = jax.jit(be._stage_prepare)(
        pk_x, pk_y, pk_mask, sig_x, sig_y, z_digits, set_mask
    )
    for i, c in enumerate(z_pk):
        out[f"prepare_zpk_{i}"] = np.asarray(c)
    for i, c in enumerate(sig_acc):
        out[f"prepare_sigacc_{i}"] = np.asarray(c)
    out["prepare_bad"] = np.asarray(bad)

    h_jac = jax.jit(h2.hash_to_g2_jacobian)(us)
    for i, c in enumerate(h_jac):
        out[f"h2c_{i}"] = np.asarray(c)

    px, py, qxx, qyy, pm = jax.jit(be._stage_pairs)(z_pk, h_jac, sig_acc, set_mask)
    for name, arr in (("px", px), ("py", py), ("qxx", qxx), ("qyy", qyy),
                      ("pair_mask", pm)):
        out[f"pairs_{name}"] = np.asarray(arr)

    ok = jax.jit(be._stage_pairing)(px, py, qxx, qyy, pm)
    out["pairing_ok"] = np.asarray(ok)
    print(f"pairing ok = {bool(out['pairing_ok'])}", flush=True)
    return out


def main():
    action, path = sys.argv[1], sys.argv[2]
    import numpy as np

    got = run_stages()
    if action == "save":
        np.savez(path, **got)
        print(f"saved {len(got)} arrays to {path}")
        return 0
    ref = np.load(path)
    order = [k for k in ref.files]
    first_bad = None
    for k in order:
        same = np.array_equal(ref[k], got[k])
        status = "OK  " if same else "DIFF"
        if not same and first_bad is None:
            first_bad = k
        print(f"{status} {k}: ref_shape={ref[k].shape}")
        if not same and ref[k].size <= 64:
            print(f"  ref: {ref[k].ravel()}")
            print(f"  got: {got[k].ravel()}")
    print("FIRST DIVERGENCE:", first_bad or "none — identical across platforms")
    return 1 if first_bad else 0


if __name__ == "__main__":
    sys.exit(main())
