"""Differential tests: JAX limbed Montgomery arithmetic vs Python bigints."""

import random
import re

import jax
import numpy as np
import pytest

from lighthouse_tpu.crypto.bls381.constants import P
from lighthouse_tpu.crypto.jaxbls import limbs as L

rng = random.Random(99)


def rand_elems(n):
    return [rng.randrange(P) for _ in range(n)]


def to_m(xs):
    return L.to_mont_jit(np.asarray(L.pack_batch(xs)))


def from_m(arr):
    return L.unpack_batch(L.from_mont_jit(arr))


def test_pack_unpack_roundtrip():
    xs = rand_elems(8) + [0, 1, P - 1]
    arr = L.pack_batch(xs)
    assert L.unpack_batch(arr) == xs


def test_mont_roundtrip():
    xs = rand_elems(8) + [0, 1, P - 1]
    assert from_m(to_m(xs)) == xs


def test_mont_mul_matches_bigint():
    xs = rand_elems(16)
    ys = rand_elems(16)
    out = from_m(L.mont_mul_jit(to_m(xs), to_m(ys)))
    assert out == [x * y % P for x, y in zip(xs, ys)]


def test_mont_sqr():
    xs = rand_elems(8)
    out = from_m(L.mont_sqr_jit(to_m(xs)))
    assert out == [x * x % P for x in xs]


def test_add_sub_neg():
    xs = rand_elems(12) + [0, P - 1]
    ys = rand_elems(12) + [P - 1, 0]
    ax, ay = to_m(xs), to_m(ys)
    assert from_m(L.add_mod_jit(ax, ay)) == [(x + y) % P for x, y in zip(xs, ys)]
    assert from_m(L.sub_mod_jit(ax, ay)) == [(x - y) % P for x, y in zip(xs, ys)]
    assert from_m(L.neg_mod_jit(ax)) == [(-x) % P for x in xs]


def test_mul_small():
    xs = rand_elems(8) + [P - 1, 0]
    ax = to_m(xs)
    for k in (2, 3, 8, 12):
        assert from_m(L.mul_small_jit(ax, k)) == [x * k % P for x in xs]


def test_pow_and_inv():
    xs = rand_elems(4)
    ax = to_m(xs)
    out = from_m(L.mont_pow_static_jit(ax, 5))
    assert out == [pow(x, 5, P) for x in xs]
    inv = from_m(L.mont_inv_jit(ax))
    assert inv == [pow(x, P - 2, P) for x in xs]


def test_edge_values():
    # worst-case operands for carry logic
    xs = [P - 1, P - 1, 1, 0, (1 << 380) % P]
    ys = [P - 1, 1, P - 1, P - 1, (1 << 383) % P]
    out = from_m(L.mont_mul_jit(to_m(xs), to_m(ys)))
    assert out == [x * y % P for x, y in zip(xs, ys)]


def test_is_zero_eq():
    xs = [0, 5, P - 1]
    arr = np.asarray(L.pack_batch(xs))
    assert list(np.asarray(L.is_zero(arr))) == [True, False, False]
    assert bool(np.all(np.asarray(L.eq(arr, arr))))


# ------------------------------------------------------------------------
# The carry form that serves, on limb patterns random operands never make.
#
# `_prefix_carry`'s propagate branch fires only where a limb sum is exactly
# 0xFFFF (a borrow: where two limbs are equal): 2^-16 a limb on random
# operands. Each pattern below spells the 24 limbs from the lowest up as
# g (generates a carry / borrow), p (propagates one), k (neither), and each
# op builds the operands that read that way to it. Python integers are the
# reference; a recorder then shows the carry really travelled.

_PATTERNS = {
    "run_1": "k" * 10 + "g" + "p" + "k" * 12,
    "run_8": "k" * 5 + "g" + "p" * 8 + "k" * 10,
    "run_23_carry_in_at_limb_0": "g" + "p" * 23,
    "all_propagate_nothing_to_carry": "p" * 24,
    "alternating": "gp" * 12,
    "run_ends_at_the_top_limb": "k" * 15 + "g" + "p" * 8,
    "two_runs_a_kill_between": "g" + "p" * 7 + "k" + "g" + "p" * 14,
}
_TOP = L.NL - 1
_N_LIMBS = [int(v) for v in L.N_HOST]


def _val(limbs):
    return sum(int(v) << (L.LB * i) for i, v in enumerate(limbs))


def _x(i):
    return (0x1357 * (i + 3)) & 0xFFFF


def _carry_pair(pattern, canonical):
    """(a, b) limb lists whose limb SUMS read as the pattern. Canonical
    operands (< P) keep the top limb under 0x1a01, where no sum reaches
    0xFFFF: their top limb is a kill whatever the pattern says."""
    a, b = [], []
    for i, s in enumerate(pattern):
        x = _x(i)
        pa, pb = {"g": (0xC123, 0x4567), "p": (x, 0xFFFF - x),
                  "k": (0x1234, 0x0101)}[s]
        if canonical and i == _TOP:
            pa, pb = 0x0D00, 0x0CFF
        a.append(pa)
        b.append(pb)
    return a, b


def _borrow_pair(pattern):
    """(a, b) canonical limb lists whose limb DIFFERENCES read as the
    pattern: g is a < b, p is a == b, k is a > b."""
    a, b = [], []
    for i, s in enumerate(pattern):
        x = _x(i) & (0x0FFF if i == _TOP else 0xFFFE)
        pa, pb = {"g": (x, x + 1), "p": (x, x), "k": (x + 1, x)}[s]
        a.append(pa)
        b.append(pb)
    return a, b


def _redundant(pattern):
    """One operand of carry_normalize with limbs up to 2^31 - 1: every high
    half is 0x7FFF, so after the fold limb i reads lo_i + 0x7FFF."""
    lo = {"g": 0x8001, "p": 0x8000, "k": 0x0000}
    return [0x7FFF0000 | (0xFFFF if i == 0 else lo[s])
            for i, s in enumerate(pattern)]


def _times_12(pattern):
    """An operand of mul_small(., 12): 12 * 0x5555 = 0x3FFFC, whose low half
    and the 3 carried up from the same limb below sum to 0xFFFF; 0x5556
    sends a 4 instead, and the limb above overflows."""
    return [1 if i == _TOP else {"g": 0x5556, "p": 0x5555, "k": 0x0001}[s]
            for i, s in enumerate(pattern)]


def _beside_the_modulus(pattern):
    """r < P whose limbs read as the pattern against P's own: mont_mul's
    last step subtracts P from r (or r + P), and the borrow runs through
    the limbs where they are equal."""
    r = []
    for s, n in zip(pattern, _N_LIMBS):
        if s == "k" and n == 0xFFFF or s == "g" and n == 0:
            s = "p"
        r.append(n + {"g": -1, "p": 0, "k": 1}[s])
    top = max((i for i, s in enumerate(pattern) if r[i] != _N_LIMBS[i]),
              default=None)
    if top is None:
        r[0] -= 1                       # all equal would be P itself
    elif r[top] > _N_LIMBS[top]:
        r[top] = _N_LIMBS[top] - 1      # the highest difference decides < P
    assert 0 <= _val(r) < P
    return r


def _u32(rows):
    return np.asarray(rows, dtype=np.uint32)


def _case(op, pattern):
    """(function, argument arrays, expected (rows of) Python integers)."""
    if op == "carry_normalize":
        a, b = _carry_pair(pattern, canonical=False)
        rows = [[x + y for x, y in zip(a, b)], _redundant(pattern)]
        want = [(_val(t) % (1 << 384), _val(t) >> 384) for t in rows]
        return L.carry_normalize, (_u32(rows),), want
    if op == "add_mod":
        a, b = _carry_pair(pattern, canonical=True)
        return L.add_mod, (_u32([a]), _u32([b])), [(_val(a) + _val(b)) % P]
    if op == "sub_mod":
        a, b = _borrow_pair(pattern)
        return (L.sub_mod, (_u32([a, b]), _u32([b, a])),
                [(_val(a) - _val(b)) % P, (_val(b) - _val(a)) % P])
    if op == "mul_small_12":
        rows = [_times_12(pattern), _carry_pair(pattern, canonical=True)[0]]
        return (lambda x: L.mul_small(x, 12), (_u32(rows),),
                [_val(r) * 12 % P for r in rows])
    assert op == "mont_mul"
    a, b = _carry_pair(pattern, canonical=True)
    r = _beside_the_modulus(pattern)
    r_inv = pow(L.R_MONT, -1, P)
    return (L.mont_mul, (_u32([r, a]), _u32([list(L.ONE_MONT), b])),
            [_val(r), _val(a) * _val(b) * r_inv % P])


def _longest_live_run(calls):
    """Over the recorded `_prefix_carry` calls: the longest run of limbs
    that propagate AND have a carry coming in."""
    best = 0
    for p, G in calls:
        live = p[..., 1:] & G[..., :-1]
        for row in live.reshape(-1, live.shape[-1]):
            run = 0
            for bit in row:
                run = run + 1 if bit else 0
                best = max(best, run)
    return best


_OPS = ("carry_normalize", "add_mod", "sub_mod", "mul_small_12", "mont_mul")
_JITS = {}


@pytest.mark.parametrize("pattern", _PATTERNS)
@pytest.mark.parametrize("op", _OPS)
def test_carry_patterns_match_python_integers(op, pattern, monkeypatch):
    fn, args, want = _case(op, _PATTERNS[pattern])
    if op != "carry_normalize":
        assert all(v < P for a in args for v in L.unpack_batch(a))
    got = _JITS.setdefault(op, jax.jit(fn))(*args)
    if op == "carry_normalize":
        out, final = got
        got_ints = list(zip(L.unpack_batch(out), [int(c) for c in final]))
    else:
        got_ints = L.unpack_batch(got)
    assert got_ints == want

    # the aim: un-jitted, with the one carry form recording what it is given
    calls = []
    real = L._prefix_carry

    def recording(g, p):
        G = real(g, p)
        calls.append((np.asarray(p), np.asarray(G)))
        return G

    monkeypatch.setattr(L, "_prefix_carry", recording)
    fn(*args)
    assert calls
    if pattern != "all_propagate_nothing_to_carry":
        longest = max(map(len, re.split("[gk]", _PATTERNS[pattern])))
        # sub_mod hands the pattern to the prefix as it is spelt; the others
        # fold or multiply first, which may cost a run its ends
        floor = longest if op == "sub_mod" else max(1, longest - 3)
        assert _longest_live_run(calls) >= floor
