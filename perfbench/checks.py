"""Every check of a run lands here; any failure makes `correct` false."""

from __future__ import annotations

import sys


class Checks:
    def __init__(self):
        self.failures = []

    def check(self, ok, what: str) -> bool:
        if not ok:
            self.failures.append(what)
            print(f"CHECK FAILED: {what}", file=sys.stderr, flush=True)
        return bool(ok)
