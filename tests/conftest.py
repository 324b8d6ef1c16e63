"""Test harness config.

All tests run JAX on a virtual 8-device CPU mesh (no real TPU needed) so
sharding/collective paths are exercised the way the reference tests exercise
multi-goroutine topologies in one process. Mirrors eKuiper's auto-mock-clock
under `go test` (pkg/timex): every test starts with a fresh mock clock.
"""
import os

# Must happen before jax import anywhere. Force CPU even when the outer
# environment selects a TPU platform — tests must not need a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Forces the CPU for tests whatever platform the installation defaults to.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Dynamic lock-order checker (utils/lockcheck.py): must install BEFORE
# any ekuiper_tpu module allocates its locks, so every engine lock is
# tracked. KUIPER_LOCKCHECK=0 opts out.
from ekuiper_tpu.utils import lockcheck  # noqa: E402

if os.environ.get("KUIPER_LOCKCHECK", "1") != "0":
    lockcheck.install()

#: cycles already reported by a teardown — later teardowns skip them
_reported_lock_cycles: set = set()

from ekuiper_tpu.utils import timex  # noqa: E402
from ekuiper_tpu.store import kv  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_engine_state():
    """Fresh mock clock + in-memory store + empty subtopo/shared-fold
    pools per test."""
    from ekuiper_tpu.planner import sharing
    from ekuiper_tpu.runtime import control, nodes_sharedfold, subtopo

    from ekuiper_tpu.observability import (devwatch, health, jitcert,
                                           kernwatch, memwatch)
    from ekuiper_tpu.runtime.events import recorder

    clock = timex.set_mock_clock(0)
    kv.setup("memory")
    nodes_sharedfold.reset()
    subtopo.reset()
    sharing.reset()
    recorder().clear()
    health.reset()
    control.reset()
    yield clock
    control.reset()
    health.reset()
    nodes_sharedfold.reset()
    subtopo.reset()
    sharing.reset()
    recorder().clear()
    devwatch.registry().clear()
    kernwatch.reset()
    memwatch.registry().clear()
    jitcert.reset()
    from ekuiper_tpu.ops import tierstore

    tierstore.reset()
    from ekuiper_tpu.parallel import sharded

    sharded.reset()
    from ekuiper_tpu.observability import meshwatch, timeline

    meshwatch.reset()
    timeline.reset()
    from ekuiper_tpu.runtime import aotcache

    aotcache.reset()
    timex.use_real_clock()
    # dynamic lock-order teardown check: the acquisition graph
    # accumulates across tests (a consistent GLOBAL order is the
    # invariant); the test that closes an ABBA cycle fails here. Only
    # NEW cycles fail — the graph is never pruned, so without the memo
    # one inversion would cascade into every later test's teardown and
    # bury the culprit
    if lockcheck.installed():
        fresh = [c for c in lockcheck.check()
                 if c not in _reported_lock_cycles]
        _reported_lock_cycles.update(fresh)
        assert not fresh, "\n".join(fresh)


@pytest.fixture
def mock_clock():
    return timex.get_mock_clock()


def wait_for_checkpoint(store, rule_id, cid, timeout=5.0):
    """Poll the persisted checkpoint until `cid` lands; returns the snap.
    Shared by the crash-replay e2e tests (test_checkpoint, test_kafka)."""
    import time

    deadline = time.time() + timeout
    while time.time() < deadline:
        snap, ok = store.kv(f"checkpoint:{rule_id}").get_ok("latest")
        if ok and snap.get("checkpoint_id") == cid:
            return snap
        time.sleep(0.01)
    raise AssertionError(f"checkpoint {cid} for {rule_id} never persisted")


def collect_window_result(mem, topic, mock_clock, advance_ms=10_000,
                          timeout=8.0):
    """Subscribe, fire the window boundary, and flatten the emissions to a
    {key_field: ...} message list."""
    import time

    got = []
    mem.subscribe(topic, lambda t, p: got.append(p))
    mock_clock.advance(advance_ms)
    deadline = time.time() + timeout
    while time.time() < deadline and not got:
        time.sleep(0.02)
    msgs = []
    for p in got:
        msgs.extend(p if isinstance(p, list) else [p])
    return msgs
