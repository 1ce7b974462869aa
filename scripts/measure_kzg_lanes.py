#!/usr/bin/env python3
"""Times the KZG batch check's lane pass (`msm.kzg_lincomb_kernel`) alone, on
the chip, at 8, 64 and 128 lanes (1, 8 and 16 blob slots of eight lanes): the
measurement behind `msm.KZG_BLOB_SLOTS` = 16 (one program, one full row of
lanes, whatever the batch holds) and PERF.md S6, PR 33.

    chiprun -- python scripts/measure_kzg_lanes.py            # slots 1 8 16
    python scripts/measure_kzg_lanes.py --rehearse            # CPU dry run

One process; each program compiles once (about half a minute on a v5e), then
five calls are timed to `block_until_ready`. Last stdout line is JSON, also
written to chiprun_out/kzg_lanes.json. Without a TPU (and without
--rehearse) it exits 2.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SLOTS = (1, 8, 16)      # blob slots of eight lanes: 8, 64 and 128 lanes
REPS = 5


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from lighthouse_tpu.crypto.bls381 import curve as cv
    from lighthouse_tpu.crypto.bls381.constants import R
    from lighthouse_tpu.crypto.jaxbls import backend as be
    from lighthouse_tpu.crypto.jaxbls import limbs as lb
    from lighthouse_tpu.crypto.jaxbls import msm
    from lighthouse_tpu.utils import jaxcfg

    d0 = jax.devices()[0]
    if d0.platform != "tpu" and not args.rehearse:
        print(json.dumps({"error": f"no TPU: platform {d0.platform!r}"}))
        return 2
    jaxcfg.setup_compilation_cache()
    lincomb = jax.jit(msm.kzg_lincomb_kernel)
    rng = np.random.default_rng(33)
    point = cv.g1_mul(cv.G1_GEN, 0xC0FFEE)
    rows = []
    for slots in SLOTS:
        lanes = slots * msm.KZG_ROWS
        px = np.tile(be.pack_ints_vec([point[0]]), (lanes, 1))
        py = np.tile(be.pack_ints_vec([point[1]]), (lanes, 1))
        live = np.ones((lanes,), np.uint32)
        scalars = [int(rng.integers(1, 2**62)) ** 4 % R for _ in range(lanes)]
        raw = np.frombuffer(b"".join(k.to_bytes(32, "big") for k in scalars),
                            np.uint8).reshape(lanes, 32)
        bits = np.unpackbits(raw, axis=1)[:, 1:].astype(np.uint32)
        assert bits.shape == (lanes, msm.KZG_SCALAR_BITS) and lb.NL == px.shape[1]
        t0 = time.perf_counter()
        jax.block_until_ready(lincomb(px, py, live, bits))
        first = time.perf_counter() - t0
        times = []
        for _ in range(1 if args.rehearse else REPS):
            t0 = time.perf_counter()
            jax.block_until_ready(lincomb(px, py, live, bits))
            times.append((time.perf_counter() - t0) * 1e3)
        rows.append({"slots": slots, "lanes": lanes,
                     "first_call_s": round(first, 2),
                     "ms_median": statistics.median(times),
                     "ms_all": [round(t, 3) for t in times]})
        print(json.dumps(rows[-1]), flush=True)
    out = {"device": {"platform": d0.platform, "kind": d0.device_kind},
           "rows": rows}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kzg_lanes.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
