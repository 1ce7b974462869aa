"""Urgent-path hybrid routing: host serves when the device is cold, absent,
or over budget (SURVEY §7 hard part (d); reference escape hatch:
/root/reference/beacon_node/beacon_chain/src/attestation_verification/batch.rs:116-120).

These tests drive the policy with a stub device so no jax dispatch is
involved; the real device path is covered by the jaxbls suites.
"""

import threading
import time

import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls import api as bls_api
from lighthouse_tpu.crypto.bls.hybrid import HybridBackend
from lighthouse_tpu.crypto.bls381 import curve as cv
from lighthouse_tpu.crypto.bls381.constants import R


@pytest.fixture(scope="module")
def one_set():
    sk = 0x1234
    pk = bls.PublicKey(cv.g1_mul(cv.G1_GEN, sk))
    msg = b"\x07" * 32
    h = bls_api.hash_to_g2_point(msg)
    sig = bls.Signature(cv.g2_mul(h, sk))
    return [bls.SignatureSet(sig, [pk], msg)]


@pytest.fixture(scope="module")
def bad_set(one_set):
    s = one_set[0]
    wrong = bls.SignatureSet(s.signature, s.signing_keys, b"\x08" * 32)
    return [wrong]


class StubDevice:
    """Counts calls; verdict and failures scriptable."""

    def __init__(self, verdict=True, fail=False, delay=0.0):
        self.verdict = verdict
        self.fail = fail
        self.delay = delay
        self.calls = 0
        self.lock = threading.Lock()

    def verify_signature_sets(self, sets, rands):
        with self.lock:
            self.calls += 1
        if self.fail:
            raise RuntimeError("device exploded")
        if self.delay:
            time.sleep(self.delay)
        return self.verdict

    def verify_signature_sets_async(self, sets, rands):
        outer = self

        class H:
            def result(self):
                return outer.verify_signature_sets(sets, rands)

        return H()


def _make(state="up", device=None, **kw):
    """HybridBackend with the probe short-circuited to a known state."""
    b = HybridBackend(probe_startup_wait_secs=0.1, probe_retry_secs=3600, **kw)
    b._probe_started.set()
    b._probe_done.set()
    b._state = state
    b._device = device
    return b


def test_device_down_serves_from_host(one_set, bad_set):
    b = _make(state="down")
    assert b.verify_signature_sets(one_set, [1]) is True
    assert b.verify_signature_sets(bad_set, [1]) is False
    # async path resolves immediately from the host too
    assert b.verify_signature_sets_async(one_set, [1]).result() is True


def test_cold_bucket_serves_host_and_warms_device(one_set):
    dev = StubDevice()
    b = _make(device=dev)
    # small + cold -> host answers NOW, device warms in the background
    assert b.verify_signature_sets(one_set, [1]) is True
    for _ in range(100):
        with b._lock:
            if b._warm_buckets:
                break
        time.sleep(0.05)
    with b._lock:
        assert b._warm_buckets, "background warm never completed"
    assert dev.calls >= 1
    # same shape again: now rides the device
    before = dev.calls
    assert b.verify_signature_sets(one_set, [1]) is True
    assert dev.calls == before + 1


def test_large_batch_goes_to_device_even_cold(one_set):
    dev = StubDevice()
    b = _make(device=dev, urgent_max_sets=4)
    big = one_set * 8   # 8 sets > urgent_max_sets
    assert b.verify_signature_sets(big, [1] * 8) is True
    assert dev.calls == 1


def test_latency_budget_reroutes_small_to_host(one_set):
    dev = StubDevice()
    b = _make(device=dev, p99_budget_ms=50.0)
    bucket = b._bucket(one_set)
    with b._lock:
        b._warm_buckets.add(bucket)
        for _ in range(16):
            b._lats.append(0.5)   # 500ms device verifies on record
    before = dev.calls
    assert b.verify_signature_sets(one_set, [1]) is True
    assert dev.calls == before, "over-budget small verify went to device"


def test_device_errors_fall_back_and_mark_down(one_set):
    dev = StubDevice(fail=True)
    b = _make(device=dev)
    bucket = b._bucket(one_set)
    with b._lock:
        b._warm_buckets.add(bucket)
    for _ in range(3):
        assert b.verify_signature_sets(one_set, [1]) is True  # host answered
    with b._lock:
        assert b._state == "down"


def test_registry_exposes_hybrid(one_set):
    prev = bls_api.get_backend()
    try:
        b = bls_api.set_backend("hybrid")
        assert b.name == "hybrid"
        assert "hybrid" in bls_api.available_backends()
        # node-start-during-outage story: force the probe result to "down"
        # and serve through the PUBLIC api entry point
        b._probe_started.set()
        b._probe_done.set()
        b._state = "down"
        assert bls_api.verify_signature_sets(one_set, lambda n: [1] * n) is True
    finally:
        bls_api._active_backend = prev
