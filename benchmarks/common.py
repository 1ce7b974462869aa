"""What run.py, check_outputs.py and the drivers share: lines and checks."""

from __future__ import annotations

import json


class BenchFailure(Exception):
    """The run broke one of its own conditions: no result line."""


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise BenchFailure(what)
