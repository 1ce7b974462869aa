#!/usr/bin/env python3
"""Times, on the chip, stage 1's two coefficient chains alone by lane width:
the table behind `backend.Z_WINDOW` and PERF.md S6, PR 45.

A job is `<group>:<lanes>:<form>`, one jitted program, ms a call, median of
5, its first call's seconds (trace, lower, compile, one run) beside it:

  group   g1 = z_i * aggpk_i (the base Jacobian, as `tree_sum` leaves it: a
          Z of its own a lane), g2 = z_i * sig_i (the base affine and its
          identity by a mask, as the marshal uploads a signature);
  lanes   set lanes of the dispatch: 4 .. 1,024, what the cells serve;
  form    parent            `co.scalar_mul_bits`: per bit a doubling and the
                            complete addition, what every dispatch ran;
          w2 | w4           `co.scalar_mul_z` at that window (w4 serves);
          w1                its additions one bit a step, no table: measured
                            and not taken (`bit_chain` below);
          w<k>c<lanes>      the same walked `lanes` at a time inside the one
                            program (`in_chunks` below): measured and not
                            taken, a chain costs by its lanes from 128 on.

Every lane's product is compared with the pure-Python curve's as an affine
point (another formula gives another Z), and a candidate's `met` flag has to
stay down: exit 1 otherwise.

    chiprun --timeout 3000 -- python3 scripts/measure_z_chain.py --budget-s 1500
    python3 scripts/measure_z_chain.py --rehearse          # CPU dry run
    ... --jobs g2:1024:parent,g2:1024:w1c256               # chosen jobs

One process. Prints one JSON line a job and writes the whole, as it grows,
to chiprun_out/z_chain.json. Without a TPU (and without --rehearse, which
runs 4 and 8 lanes: its first-call seconds are the forms' compile seconds on
XLA:CPU) it exits 2. Not part of the benchmark; rerun it when the curve
arithmetic or the chip changes.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lighthouse_tpu.crypto.bls381 import curve as cv
from lighthouse_tpu.crypto.bls381 import fields as fl
from lighthouse_tpu.crypto.bls381.constants import P
from lighthouse_tpu.crypto.jaxbls import curve_ops as co
from lighthouse_tpu.crypto.jaxbls import tower as tw
from lighthouse_tpu.utils import jaxcfg

REPS = 5
OUT = "chiprun_out/z_chain.json"
FORMS = ("parent", "w1", "w4")
#: the full table, most wanted first (a run under --budget-s stops where
#: its seconds end: ~30 s of compile a program): every form at the widths
#: the cells serve, the wide chains walked in chunks, then the widths
#: between and the two-bit window
WIDTHS = (1024, 256, 4, 64, 128, 16, 512)
CHUNKED = ("g2:1024:w1c256", "g2:1024:w4c256", "g2:1024:w1c128",
           "g2:1024:w1c512", "g2:1024:w4c128", "g1:1024:w1c256",
           "g1:1024:w4c256", "g2:512:w4c256")


def full_table() -> list:
    first, rest = WIDTHS[:5], WIDTHS[5:]
    jobs = [f"{g}:{w}:{f}" for w in first[:1] for g in ("g2", "g1")
            for f in FORMS]
    jobs += CHUNKED[:4]
    jobs += [f"{g}:{w}:{f}" for w in first[1:] for g in ("g2", "g1")
             for f in FORMS]
    jobs += CHUNKED[4:]
    jobs += [f"{g}:{w}:{f}" for w in rest for g in ("g2", "g1") for f in FORMS]
    jobs += [f"{g}:{w}:w2" for w in (1024, 256, 4, 64) for g in ("g2", "g1")]
    return jobs


def parse_form(form: str):
    """'parent' -> None; 'w4c256' -> (4, 256); 'w1' -> (1, None)."""
    if form == "parent":
        return None
    window, _, chunk = form[1:].partition("c")
    return int(window), (int(chunk) if chunk else None)


def bit_chain(p, bits, ops, p_inf=None):
    """`co.scalar_mul_z`'s step without its table: per bit a doubling and an
    addition of p, taken where the bit is set. The table's `w1`."""
    if len(p) == 2:
        p_jac = co.affine_to_jac(ops, p, inf_mask=p_inf)
    else:
        p_jac, p_inf = p, ops.is_zero(p[2])
    none = jax.tree_util.tree_map(
        lambda c, x: jnp.broadcast_to(c, x.shape), co.identity(ops), p_jac)

    def bit_step(carry, bit):
        acc, met = carry
        acc = co._z_double(acc, ops)
        added, now = co._z_add_finite(acc, p, p_jac, p_inf, ops)
        take = bit == 1
        return (co.pt_select(ops, take, added, acc),
                jnp.logical_or(met, jnp.logical_and(now, take))), None

    (acc, met), _ = jax.lax.scan(
        bit_step, (none, jnp.zeros(p_inf.shape, bool)),
        jnp.moveaxis(bits, -1, 0))
    return acc, met


def in_chunks(fn, lanes, chunk: int):
    """`fn` over the leading (lane) axis of every leaf of `lanes`, `chunk`
    lanes at a time inside the one program (a lax.map over the axis viewed
    as (n / chunk, chunk)); the whole axis at once where chunk covers it."""
    n = lanes[0].shape[0]
    if chunk >= n:
        return fn(lanes)
    assert n % chunk == 0, (n, chunk)
    out = jax.lax.map(fn, jax.tree_util.tree_map(
        lambda x: x.reshape((n // chunk, chunk) + x.shape[1:]), lanes))
    return jax.tree_util.tree_map(
        lambda x: x.reshape((n,) + x.shape[2:]), out)


def program(group: str, form: str):
    """The jitted chain of a job: (x, y, z, bits) for g1, (x, y, finite,
    bits) for g2, to (product, met) — `met` all down for the parent, whose
    addition is complete."""
    ops = co.FQ_OPS if group == "g1" else co.FQ2_OPS
    plan = parse_form(form)

    def base(lanes):
        a, b, c, bits = lanes
        if group == "g1":
            return (a, b, c), None, bits
        return (a, b), jnp.logical_not(c), bits

    def parent(lanes):
        p, p_inf, bits = base(lanes)
        if p_inf is not None:
            p = co.affine_to_jac(ops, p, inf_mask=p_inf)
        return co.scalar_mul_bits(p, bits, ops), jnp.zeros(bits.shape[:1], bool)

    def chain(lanes):
        p, p_inf, bits = base(lanes)
        if plan[0] == 1:
            return bit_chain(p, bits, ops, p_inf=p_inf)
        return co.scalar_mul_z(p, bits, ops, p_inf=p_inf, window=plan[0])

    if plan is None:
        return jax.jit(lambda *lanes: parent(lanes))
    return jax.jit(lambda *lanes: in_chunks(
        chain, lanes, plan[1] or lanes[0].shape[0]))


def unpack(coords) -> list:
    """Device Fq (n, NL) or Fq2 (n, 2, NL) coordinates as host ints or
    pairs of them."""
    flat = tw.fq_batch_from_device(coords.reshape(-1, coords.shape[-1]))
    if coords.ndim == 2:
        return flat
    return list(zip(flat[0::2], flat[1::2]))


def same_point(group: str, jac, affine) -> bool:
    """Host: Jacobian (X, Y, Z) against affine (x, y) or None, X = x Z^2 and
    Y = y Z^3, the identity Z = 0: no inversion on the device."""
    X, Y, Z = jac
    if group == "g1":
        if affine is None or Z == 0:
            return affine is None and Z == 0
        return (X == affine[0] * Z * Z % P
                and Y == affine[1] * Z * Z * Z % P)
    if affine is None or Z == (0, 0):
        return affine is None and Z == (0, 0)
    zz = fl.fq2_sqr(Z)
    return (X == fl.fq2_mul(affine[0], zz)
            and Y == fl.fq2_mul(affine[1], fl.fq2_mul(zz, Z)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--jobs", default=None,
                    help="comma-separated group:lanes:form, in place of the "
                         "full table")
    ap.add_argument("--budget-s", type=float, default=None,
                    help="start no job once this many seconds have passed")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    d0 = jax.devices()[0]
    if d0.platform != "tpu" and not args.rehearse:
        print(json.dumps({"error": f"no TPU: platform {d0.platform!r}"}))
        return 2
    jaxcfg.setup_compilation_cache()
    reps = 1 if args.rehearse else REPS
    if args.jobs:
        jobs = args.jobs.split(",")
    elif args.rehearse:
        jobs = ([f"{g}:4:{f}" for g in ("g1", "g2") for f in FORMS + ("w2",)]
                + [f"{g}:8:w1c4" for g in ("g1", "g2")])
    else:
        jobs = full_table()
    jobs = [tuple(j.split(":")) for j in jobs]
    n_max = max(int(lanes) for _, lanes, _ in jobs)
    out = {"device": {"platform": d0.platform, "kind": d0.device_kind},
           "rows": []}

    def save():
        os.makedirs("chiprun_out", exist_ok=True)
        with open(OUT, "w") as f:
            json.dump(out, f, indent=1)

    # the host's side, once for the widest job: bases (i + 2) G, a lane in
    # sixteen the identity, coefficients 64-bit with the corner ones first
    rng = np.random.default_rng(45)
    zs = [1, 2, 1 << 63, (1 << 64) - 1, (1 << 43) + 5] + [
        int.from_bytes(rng.bytes(8), "big") | 1 for _ in range(n_max)]
    zs = zs[:n_max]
    bits = co.scalars_to_bits(zs, 64)
    t0 = time.perf_counter()
    g1, g2, p1, p2 = [], [], cv.G1_GEN, cv.G2_GEN
    for i in range(n_max):
        p1, p2 = cv.g1_add(p1, cv.G1_GEN), cv.g2_add(p2, cv.G2_GEN)
        g1.append(None if i % 16 == 7 else p1)
        g2.append(None if i % 16 == 7 else p2)
    want = {"g1": [cv.g1_mul(p, z) if p else None for p, z in zip(g1, zs)],
            "g2": [cv.g2_mul(p, z) if p else None for p, z in zip(g2, zs)]}
    out["host_reference_s"] = round(time.perf_counter() - t0, 1)
    # g1's base with a Z of its own a lane: (x z^2, y z^3, z)
    scale = [int.from_bytes(rng.bytes(47), "big") + 2 for _ in range(n_max)]
    x1, y1, z1 = (np.asarray(c) for c in co.g1_batch_to_device([
        None if p is None else (p[0] * s * s % P, p[1] * s ** 3 % P)
        for p, s in zip(g1, scale)]))
    z1 = np.where((z1 != 0).any(-1, keepdims=True),
                  np.asarray(tw.fq_batch_to_device(scale)), z1)
    x2, y2, z2 = (np.asarray(c) for c in co.g2_batch_to_device(g2))
    finite2 = (z2 != 0).any((-1, -2))
    lanes_of = {"g1": (x1, y1, z1, bits), "g2": (x2, y2, finite2, bits)}
    ok = True

    for group, lanes, form in jobs:
        if args.budget_s and time.perf_counter() - t_start > args.budget_s:
            out["left_out"] = [":".join(j) for j in jobs[len(out["rows"]):]]
            break
        n = int(lanes)
        fn = program(group, form)
        host_args = tuple(a[:n] for a in lanes_of[group])
        t0 = time.perf_counter()
        res = jax.block_until_ready(fn(*host_args))
        first = time.perf_counter() - t0
        ms = []
        placed = jax.block_until_ready(jax.device_put(host_args))
        for _ in range(reps):
            t0 = time.perf_counter()
            res = jax.block_until_ready(fn(*placed))
            ms.append((time.perf_counter() - t0) * 1e3)
        prod, met = res
        same = not bool(np.asarray(met).any()) and all(
            same_point(group, jac, affine) for jac, affine in
            zip(zip(*(unpack(np.asarray(c)) for c in prod)), want[group]))
        ok = ok and same
        out["rows"].append({
            "group": group, "lanes": n, "form": form,
            "first_call_s": round(first, 2),
            "ms_median": statistics.median(ms),
            "ms_all": [round(t, 3) for t in ms],
            "equals_host_products": same})
        print(json.dumps(out["rows"][-1]), flush=True)
        save()

    out["elapsed_s"] = round(time.perf_counter() - t_start, 1)
    save()
    print(json.dumps({k: v for k, v in out.items() if k != "rows"}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
