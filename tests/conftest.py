"""Test configuration.  NOTE: no XLA_FLAGS here — smoke tests must see the
real single CPU device; multi-device tests force host devices in a
subprocess of their own."""
import os
import sys

# src/ for the repro package; repo root so `benchmarks` (the harness the
# bench smoke test drives) is importable regardless of invocation cwd.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Pinned hypothesis profiles: CI runs the slow differential jobs with
# HYPOTHESIS_PROFILE=ci, which derandomizes example generation (the seed
# derives from each test's source, not the clock/database), so a red
# mm-differential job reproduces locally with the same examples and two
# CI runs of the same commit explore the same inputs.  Local runs keep
# the default randomized profile for wider exploration.
try:
    from hypothesis import settings as _hyp_settings

    _hyp_settings.register_profile("ci", derandomize=True, deadline=None,
                                   print_blob=True)
    _hyp_settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE",
                                              "default"))
except ImportError:        # hypothesis extra not installed: seeded suites
    pass                   # still provide full coverage
