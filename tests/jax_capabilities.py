"""Capability probes for environment-dependent tier-1 tests.

One JAX is installed (0.9.0, here and on the chip machine), so nothing
probes its surface. What still varies is the host: tests that spawn
whole worker processes need wall-clock headroom a single-core runner
cannot give, and fail there for reasons that have nothing to do with
the code under test. The probe pins that dependence explicitly: the
test skips — visibly, with the capability named in the reason —
instead of failing, and on a host that HAS it the test still runs and
still gates.
"""

import os

import pytest


def _usable_cpus() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # non-Linux: no affinity API
        return os.cpu_count() or 1


# Multi-process gang tests (deploy gangs, multihost meshes, cross-host
# KVBM) fork 2-3 worker processes that each compile XLA programs and
# then rendezvous over gloo collectives with a fixed connect timeout.
# On a single-core host the ranks compile SERIALLY, the rendezvous
# window expires, and the run dies with "Gloo context initialization
# failed: Connect timeout" or the parent test's own deadline — neither
# of which says anything about the code under test.
requires_multicore = pytest.mark.skipif(
    _usable_cpus() < 2,
    reason="multi-process gang tests need >=2 usable CPUs: concurrent "
           "rank compilation outlives gloo connect timeouts on a "
           "single-core host")
