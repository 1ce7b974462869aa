#!/usr/bin/env python3
"""Times the square root's exponentiation inside hash-to-G2 alone, on the
chip: the measurement behind `h2c_ops.SQRT_WINDOW` and PERF.md S6, PR 38.

Three forms of the one power `a^E`, `E = (p^2 - 9)/16` (758 bits):

  old   the one-base chain every PR up to 37 served: 4-bit fixed window,
        189 scan steps of 4 squarings + 1 table multiply (kept here only,
        as the reference: `h2c_ops` holds one chain);
  2+2   `h2c_ops.fq2_pow_frobenius` with 2 bits of e1 and 2 of e0 a joint
        digit: a 16-entry table, 190 steps of 2 squarings + 1 multiply;
  3+3   the same with 3 + 3 bits: a 64-entry table, 126 steps of 3 + 1.

Part 1: each form compiled alone on the lanes SSWU gives it at the three
served buckets, (2, n, 2, NL) for n = 4, 64, 256 sets = 8, 128, 512 lanes;
ms a call, median of 5; both joint forms checked equal, limb for limb, to
the old one. Part 2: the whole `hash_to_g2_jacobian` (jitted as the backend
jits it on the chip: its input donated) at 4, 64 and 256 sets with each
form inside, the Jacobian points checked limb-equal too.

    chiprun -- python3 scripts/measure_h2c_chain.py [--budget-s N] \
        [--jobs 256:3+3,256:old]
    python3 scripts/measure_h2c_chain.py --rehearse        # CPU dry run

One process. No compile of part 2 starts once N seconds (default 1500)
have passed; part 2 runs the gossip bucket first and the old form last;
`--jobs` runs part 2 alone, on the listed sets:form (a whole program
compiles in ~3 minutes on the chip).
Prints one JSON object and writes it, as it grows, to
chiprun_out/h2c_chain.json. Without a TPU (and without --rehearse) it
exits 2. Not part of the benchmark; rerun it when the tower arithmetic or
the chip changes.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from lighthouse_tpu.crypto.bls381.constants import P
from lighthouse_tpu.crypto.jaxbls import h2c_ops as h2
from lighthouse_tpu.crypto.jaxbls import limbs as lb
from lighthouse_tpu.crypto.jaxbls import tower as tw
from lighthouse_tpu.utils import jaxcfg

SETS = (4, 64, 256)           # the served buckets' set counts: 2n lanes
FORMS = ("old", "2+2", "3+3")
# part 2's order: what decides the constant first, the reference last
WHOLE_JOBS = (
    (64, "2+2"), (64, "3+3"), (64, "old"), (4, "2+2"), (4, "3+3"),
    (256, "2+2"), (256, "3+3"), (4, "old"), (256, "old"),
)
REPS = 5
OUT = "chiprun_out/h2c_chain.json"


def old_chain(a, window: int = 4):
    """a^E as PR 37's `fq2_pow_static(a, _E_BITS)` computed it."""
    e, digits = h2._E, []
    while e:
        digits.append(e & ((1 << window) - 1))
        e >>= window
    digits.reverse()
    nt = 1 << window
    table = [jnp.broadcast_to(tw.FQ2_ONE, a.shape), a]
    while len(table) < nt:
        m = len(table)
        idx = list(range(m, min(2 * (m - 1), nt - 1) + 1))
        prod = tw.fq2_mul(
            jnp.stack([table[j // 2] for j in idx]),
            jnp.stack([table[j - j // 2] for j in idx]),
        )
        table.extend(prod[k] for k in range(len(idx)))
    table_arr = jnp.stack(table)

    def body(acc, digit):
        for _ in range(window):
            acc = tw.fq2_sqr(acc)
        acc = tw.fq2_mul(acc, lax.dynamic_index_in_dim(table_arr, digit, 0, keepdims=False))
        return acc, None

    acc, _ = lax.scan(body, table_arr[digits[0]],
                      jnp.asarray(np.array(digits[1:], np.uint32)))
    return acc


@contextlib.contextmanager
def served_form(form: str):
    """`h2c_ops` serving `form` while a program is traced."""
    kept = h2.SQRT_WINDOW, h2.fq2_pow_frobenius
    if form == "old":
        h2.fq2_pow_frobenius = lambda a, e1, e0: old_chain(a)
    else:
        h2.SQRT_WINDOW = int(form[0])
    try:
        yield
    finally:
        h2.SQRT_WINDOW, h2.fq2_pow_frobenius = kept


def chain_program(form: str):
    def chain(a):
        with served_form(form):
            return h2.fq2_pow_frobenius(a, h2._E1, h2._E0)

    return jax.jit(chain)


def whole_program(form: str):
    # the stage's own name and donation, so the form `h2c_ops` ships lowers
    # to the served program
    def hash_to_g2_jacobian(us):
        with served_form(form):
            return h2.hash_to_g2_jacobian(us)

    return jax.jit(hash_to_g2_jacobian, donate_argnums=(0,))


def timed(fn, host_arg, reps: int):
    """(first call s, [ms a call], last result): the argument placed anew
    before each call's clock starts, as a donating program needs."""
    def placed():
        return jax.block_until_ready(jax.device_put(host_arg))

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(placed()))
    first = time.perf_counter() - t0
    ms = []
    for _ in range(reps):
        a = placed()
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(a))
        ms.append((time.perf_counter() - t0) * 1e3)
    return first, ms, out


def row(first, ms, **keys):
    return {**keys, "first_call_s": round(first, 2),
            "ms_median": statistics.median(ms),
            "ms_all": [round(t, 3) for t in ms]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--budget-s", type=float, default=1500.0)
    ap.add_argument("--jobs", default="")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    d0 = jax.devices()[0]
    if d0.platform != "tpu" and not args.rehearse:
        print(json.dumps({"error": f"no TPU: platform {d0.platform!r}"}))
        return 2
    jaxcfg.setup_compilation_cache()
    sets = (2,) if args.rehearse else SETS
    reps = 1 if args.rehearse else REPS
    whole_jobs = [(2, f) for f in FORMS] if args.rehearse else WHOLE_JOBS
    if args.jobs:
        whole_jobs = [(int(n), form) for n, form in
                      (job.split(":") for job in args.jobs.split(","))]
    out = {"device": {"platform": d0.platform, "kind": d0.device_kind},
           "shipped_window": h2.SQRT_WINDOW, "chain": [], "whole": []}

    def save():
        os.makedirs("chiprun_out", exist_ok=True)
        with open(OUT, "w") as f:
            json.dump(out, f, indent=1)

    rng = np.random.default_rng(38)

    def fq():
        return int.from_bytes(rng.bytes(48), "big") % P

    for n in () if args.jobs else sets:
        # 0, 1, a real and a pure-imaginary element among the random ones
        elems = [(0, 0), (1, 0), (fq(), 0), (0, fq())]
        elems += [(fq(), fq()) for _ in range(2 * n - len(elems))]
        a = np.asarray(tw.fq2_batch_to_device(elems)).reshape(2, n, 2, lb.NL)
        ref = None
        for form in FORMS:
            first, ms, got = timed(chain_program(form), a, reps)
            got = np.asarray(got)
            ref = got if form == "old" else ref
            out["chain"].append(row(first, ms, form=form, lanes=2 * n,
                                    limb_equal_old=bool(np.array_equal(got, ref))))
            print(json.dumps(out["chain"][-1]), flush=True)
            save()

    us = {n: lb.pack_batch([fq() for _ in range(4 * n)]).reshape(n, 2, 2, lb.NL)
          for n in sorted({n for n, _ in whole_jobs})}
    whole_ref = {}
    for n, form in whole_jobs:
        if time.perf_counter() - t_start > args.budget_s:
            out.setdefault("skipped", []).append([n, form])
            continue
        first, ms, got = timed(whole_program(form), us[n], reps)
        got = np.stack([np.asarray(c) for c in got])
        same = np.array_equal(got, whole_ref.setdefault(n, got))
        out["whole"].append(row(first, ms, form=form, sets=n,
                                limb_equal_first_form=bool(same)))
        print(json.dumps(out["whole"][-1]), flush=True)
        save()

    out["elapsed_s"] = round(time.perf_counter() - t_start, 1)
    save()
    print(json.dumps(out))
    ok = all(r["limb_equal_old"] for r in out["chain"]) and all(
        r["limb_equal_first_form"] for r in out["whole"])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
