"""Vectorized epoch processing: balance/reward/penalty vectors as arrays.

The altair+ epoch transition's per-validator loops (flag deltas,
inactivity penalties, effective-balance hysteresis — state_transition/
epoch.py) are embarrassingly data-parallel: every validator's delta is a
pure function of its own row plus a handful of epoch scalars. This module
expresses them ONCE over an abstract array namespace `xp` — the same
shared-schedule trick as ssz/sha256_batch.compress — so the host lane
(numpy uint64) and the device lane (jnp uint64 under a scoped
`jax.enable_x64`; jaxbls' uint32 limb kernels are untouched
by the scope) trace identical integer arithmetic, and both are pinned
bit-exact against the pure-Python spec path in tests/test_jaxhash.py.

Overflow honesty: all spec math is floor division over uint64. The worst
realistic numerators (base_reward * weight * flag_increments ~ 2^62 at
2M-validator scale; eff * inactivity_score) fit, and `altair_deltas`
CHECKS the actual bounds with Python bigints before vectorizing — a state
that would wrap falls back to the pure-Python path instead of silently
wrapping.

Routing: `altair_deltas` / `effective_balance_updates` return None unless
the jaxhash backend is device-backed (router.hash_backend() in
device/hybrid) AND the registry is at least `min_validators` — the
callers in state_transition/epoch.py then run the unchanged pure-Python
loops, so a default (host) node is byte-identical to pre-jaxhash.
"""

from __future__ import annotations

import os

import numpy as np

from ..state_transition import accessors as acc
from ..types import helpers as h
from ..types.spec import ForkName
from ..utils.logging import get_logger

DEFAULT_MIN_VALIDATORS = 1024

_log = get_logger("jaxhash.epoch")
_kernel_cache: dict = {}


def min_validators() -> int:
    raw = os.environ.get("LIGHTHOUSE_TPU_EPOCH_VEC_MIN", "").strip()
    if raw:
        try:
            return max(1, int(raw))
        except ValueError:
            pass
    return DEFAULT_MIN_VALIDATORS


def _enabled(n: int) -> bool:
    """Route the epoch vectors? Shares the tree-hash router's breaker:
    in hybrid mode a wedged device refuses O(1) here too (the router.py
    contract) instead of paying a failed jit attempt per epoch forever;
    backend "device" keeps attempting, like the hash route. A half-open
    allow claims the probe — _device_altair_deltas reports the outcome."""
    from .router import ROUTER, hash_backend

    backend = hash_backend()
    if backend not in ("device", "hybrid") or n < min_validators():
        return False
    if backend == "hybrid" and not ROUTER.allow_device():
        return False
    return True


# ------------------------------------------------- shared vector formulation


def flag_deltas_vec(xp, eff, participating, eligible, base_per_incr, incr,
                    weight, flag_incr, total_incr, leaking, is_head):
    """(rewards, penalties) uint64 vectors for ONE participation flag —
    the vector form of epoch.get_flag_index_deltas' per-validator body.
    Scalars are Python ints (they promote to the array dtype in both
    namespaces); masks are bool arrays."""
    base = (eff // incr) * base_per_incr
    zero = xp.zeros_like(eff)
    if leaking:
        rewards = zero
    else:
        rewards = xp.where(
            participating & eligible,
            base * weight * flag_incr // (total_incr * acc.WEIGHT_DENOMINATOR),
            zero,
        )
    if is_head:
        penalties = zero
    else:
        penalties = xp.where(
            eligible & ~participating, base * weight // acc.WEIGHT_DENOMINATOR,
            zero,
        )
    return rewards, penalties


def inactivity_deltas_vec(xp, eff, scores, participating_target, eligible,
                          denom):
    """Inactivity-leak penalty vector (epoch.get_inactivity_penalty_deltas;
    rewards are identically zero there)."""
    return xp.where(
        eligible & ~participating_target, eff * scores // denom,
        xp.zeros_like(eff),
    )


def effective_balance_vec(xp, balances, eff, incr, downward, upward, max_eff):
    """(changed mask, new effective balance) for the hysteresis update
    (epoch.process_effective_balance_updates, pre-electra rule)."""
    changed = (balances + downward < eff) | (eff + upward < balances)
    new = xp.minimum(balances - balances % incr, xp.full_like(balances, max_eff))
    return changed, new


# --------------------------------------------------------- state -> arrays


def _seq_array(seq, dtype, n: int) -> np.ndarray:
    """Marshal a state field to an array: chunk-wise for CowList-backed
    fields (no per-element Python iteration at the top), fromiter for
    plain lists."""
    to_numpy = getattr(seq, "to_numpy", None)
    if to_numpy is not None:
        return to_numpy(dtype)
    return np.fromiter(seq, dtype, n)


def _registry_arrays(state):
    vals = state.validators
    n = len(vals)
    eff = np.fromiter((v.effective_balance for v in vals), np.uint64, n)
    slashed = np.fromiter((bool(v.slashed) for v in vals), bool, n)
    activation = np.fromiter((v.activation_epoch for v in vals), np.uint64, n)
    exit_ep = np.fromiter((v.exit_epoch for v in vals), np.uint64, n)
    return eff, slashed, activation, exit_ep


def _active_mask(activation, exit_ep, epoch: int):
    e = np.uint64(epoch)
    return (activation <= e) & (e < exit_ep)


# ------------------------------------------------------------- device lane


def _device_epoch_kernel(n_bucket: int):
    """One jitted kernel per padded registry bucket computing all three
    flag delta pairs + the inactivity penalty vector. Built and called
    under a scoped enable_x64 (uint64 spec arithmetic); epoch scalars ride
    as traced 0-d arrays so they never fork the compile cache."""
    key = f"epoch_{n_bucket}"
    if key in _kernel_cache:
        return _kernel_cache[key]
    import jax
    import jax.numpy as jnp

    from ..utils.jaxcfg import setup_compilation_cache

    setup_compilation_cache()

    def kernel(eff, part, eligible, target_part, scores,
               base_per_incr, incr, flag_incrs, total_incr, denom, leaking):
        rewards = []
        penalties = []
        zero = jnp.zeros_like(eff)
        base = (eff // incr) * base_per_incr
        for f, weight in enumerate(acc.PARTICIPATION_FLAG_WEIGHTS):
            participating = part[f]
            rewards.append(jnp.where(
                participating & eligible & ~leaking,
                base * weight * flag_incrs[f]
                // (total_incr * acc.WEIGHT_DENOMINATOR),
                zero,
            ))
            if f == acc.TIMELY_HEAD_FLAG_INDEX:
                penalties.append(zero)
            else:
                penalties.append(jnp.where(
                    eligible & ~participating,
                    base * weight // acc.WEIGHT_DENOMINATOR, zero,
                ))
        inact = jnp.where(
            eligible & ~target_part, eff * scores // denom, zero,
        )
        return jnp.stack(rewards), jnp.stack(penalties), inact

    _kernel_cache[key] = jax.jit(kernel)
    return _kernel_cache[key]


def _pad(arr, n_bucket):
    if arr.shape[0] == n_bucket:
        return arr
    out = np.zeros((n_bucket,) + arr.shape[1:], arr.dtype)
    out[: arr.shape[0]] = arr
    return out


# ----------------------------------------------------------- public entries


def altair_deltas(state, spec, fork, eligible):
    """The four (rewards, penalties) delta sets of
    process_rewards_and_penalties_altair as plain int lists, computed
    vectorized — or None when the jaxhash backend keeps the pure-Python
    path (host backend, small registry, or a value range that would
    overflow uint64). Bit-exact with the scalar loops by construction."""
    n = len(state.validators)
    if not _enabled(n) or acc.get_current_epoch(state, spec) == 0:
        return None
    prev = acc.get_previous_epoch(state, spec)
    cur = acc.get_current_epoch(state, spec)
    eff, slashed, activation, exit_ep = _registry_arrays(state)
    part_prev = _seq_array(state.previous_epoch_participation, np.uint8, n)
    scores = _seq_array(state.inactivity_scores, np.uint64, n)
    active_cur = _active_mask(activation, exit_ep, cur)
    active_prev = _active_mask(activation, exit_ep, prev)
    eligible_mask = np.zeros(n, bool)
    eligible_mask[list(eligible)] = True

    incr = spec.effective_balance_increment
    total_active = max(incr, int(eff[active_cur].sum()))
    base_per_incr = (
        incr * spec.base_reward_factor // acc._integer_squareroot(total_active)
    )
    leaking = acc.is_in_inactivity_leak(state, spec)
    part_masks = [
        active_prev & ~slashed & ((part_prev >> f) & 1).astype(bool)
        for f in range(len(acc.PARTICIPATION_FLAG_WEIGHTS))
    ]
    flag_balances = [max(incr, int(eff[m].sum())) for m in part_masks]
    if fork == ForkName.altair:
        quotient = spec.inactivity_penalty_quotient_altair
    else:
        quotient = spec.inactivity_penalty_quotient_bellatrix
    denom = spec.inactivity_score_bias * quotient

    # overflow honesty: check the ACTUAL bounds with bigints; a state that
    # would wrap uint64 keeps the pure-Python bigint path
    max_base = (int(eff.max(initial=0)) // incr) * base_per_incr
    max_weight = max(acc.PARTICIPATION_FLAG_WEIGHTS)
    max_flag_incr = max(fb // incr for fb in flag_balances)
    if (
        max_base * max_weight * max(1, max_flag_incr) >= 2**64
        or int(eff.max(initial=0)) * int(scores.max(initial=0)) >= 2**64
        or denom >= 2**64
    ):
        return None

    total_incr = total_active // incr
    flag_incrs = [fb // incr for fb in flag_balances]
    target_part = part_masks[acc.TIMELY_TARGET_FLAG_INDEX]

    out = _device_altair_deltas(
        n, eff, part_masks, eligible_mask, target_part, scores,
        base_per_incr, incr, flag_incrs, total_incr, denom, leaking,
    )
    if out is None:
        # host-numpy lane: the same shared formulation, no device
        rew3, pen3 = [], []
        for f, weight in enumerate(acc.PARTICIPATION_FLAG_WEIGHTS):
            r, p = flag_deltas_vec(
                np, eff, part_masks[f], eligible_mask, base_per_incr, incr,
                weight, flag_incrs[f], total_incr, leaking,
                f == acc.TIMELY_HEAD_FLAG_INDEX,
            )
            rew3.append(r)
            pen3.append(p)
        inact = inactivity_deltas_vec(
            np, eff, scores, target_part, eligible_mask, denom
        )
    else:
        rew3, pen3, inact = out
    deltas = [
        (rew3[f].tolist(), pen3[f].tolist())
        for f in range(len(acc.PARTICIPATION_FLAG_WEIGHTS))
    ]
    deltas.append(([0] * n, inact.tolist()))
    return deltas


def _device_altair_deltas(n, eff, part_masks, eligible_mask, target_part,
                          scores, base_per_incr, incr, flag_incrs,
                          total_incr, denom, leaking):
    """Device leg: padded bucketed jit under scoped x64. Returns the
    (rewards(3), penalties(3), inactivity) arrays trimmed to n, or None
    on any device failure (the caller's host-numpy lane serves). Only a
    DEVICE-served computation observes jaxhash_device_seconds — the
    host-numpy fallback must not masquerade as device latency."""
    import time

    from ..observability.device_ledger import LEDGER
    from ..ssz.core import next_pow2
    from .engine import _DEVICE_SECONDS

    t0 = time.perf_counter()
    # the epoch workload has no dispatcher — it books its device time in
    # the process-wide ledger directly, as the `epoch` tenant
    interval = LEDGER.open(
        "epoch", lane="batch", bucket=None, est_cost=None
    )
    try:
        import jax

        nb = next_pow2(n)
        interval.bucket = nb
        interval.start()
        with jax.enable_x64():
            kernel = _device_epoch_kernel(nb)
            part = np.stack([_pad(m, nb) for m in part_masks])
            rew, pen, inact = kernel(
                _pad(eff, nb), part, _pad(eligible_mask, nb),
                _pad(target_part, nb), _pad(scores, nb),
                np.uint64(base_per_incr), np.uint64(incr),
                np.asarray(flag_incrs, np.uint64), np.uint64(total_incr),
                np.uint64(denom), np.bool_(leaking),
            )
            rew = np.asarray(rew)[:, :n]
            pen = np.asarray(pen)[:, :n]
            inact = np.asarray(inact)[:n]
        _DEVICE_SECONDS.labels("epoch_deltas").observe(
            time.perf_counter() - t0
        )
        interval.close("ok")
        _router_record(True)
        return list(rew), list(pen), inact
    except Exception as e:  # device down/misconfigured: host lane serves
        interval.close("error")
        _log.warn("device epoch deltas failed; host vector lane serves",
                  error=f"{type(e).__name__}: {e}")
        _router_record(False)
        return None


def _router_record(ok: bool) -> None:
    """Report a device epoch attempt to the shared breaker — never raises
    (the delta math must not die on a diagnostics path)."""
    try:
        from .router import ROUTER

        ROUTER.record_device(ok)
    except Exception:
        pass


def effective_balance_updates(state, spec):
    """[(index, new_effective_balance)] for validators the hysteresis
    rule changes (epoch.process_effective_balance_updates, pre-electra) —
    or None when the pure-Python loop should run. The caller applies the
    copy_with writes so the memoized-root invalidation semantics are
    identical to the scalar path."""
    n = len(state.validators)
    if not _enabled(n):
        return None
    eff = np.fromiter(
        (v.effective_balance for v in state.validators), np.uint64, n
    )
    balances = _seq_array(state.balances, np.uint64, n)
    hysteresis_incr = spec.effective_balance_increment // spec.hysteresis_quotient
    downward = hysteresis_incr * spec.hysteresis_downward_multiplier
    upward = hysteresis_incr * spec.hysteresis_upward_multiplier
    changed, new = effective_balance_vec(
        np, balances, eff, spec.effective_balance_increment, downward,
        upward, spec.max_effective_balance,
    )
    return [(int(i), int(new[i])) for i in np.flatnonzero(changed)]
