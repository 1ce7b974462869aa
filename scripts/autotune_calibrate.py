#!/usr/bin/env python
"""Calibrate the BLS verification autotuner for this device.

Measures each padding bucket of the jaxbls pipeline against the committed
bench fixtures and writes a versioned device profile (JSON) that the node
autoloads at bring-up to derive its batch caps, hybrid routing budget, and
startup warmup plan (lighthouse_tpu/autotune/).

    # real device calibration (run inside a TPU session):
    python scripts/autotune_calibrate.py

    # CPU smoke: tiny fixtures, pure-python measurement backend, output to
    # a gitignored path (./autotune_profile_smoke.json) — never touches a
    # device, never clobbers an on-device profile:
    python scripts/autotune_calibrate.py --smoke

All logic lives in lighthouse_tpu.autotune.calibrate (shared with the
`autotune calibrate` CLI subcommand); this wrapper only fixes sys.path for
a checkout run. The smoke output default lands in the repo root, where
.gitignore covers it.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lighthouse_tpu.autotune.calibrate import cli_main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(cli_main())
