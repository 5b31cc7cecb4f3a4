"""Ray-equivalent runtime: task pool, stateful actors, error propagation,
and the parent-death guard."""

import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from analytics_zoo_tpu.ray import ObjectRef, RayContext, RayTaskError


def _square(x):
    return x * x


def _boom():
    raise ValueError("intentional")


class Counter:
    def __init__(self, start=0):
        self.v = start

    def add(self, k):
        self.v += k
        return self.v

    def value(self):
        return self.v


class BadActor:
    """Raises during construction (must be module-level: payloads cross
    process boundaries by pickle, same contract as ray)."""

    def __init__(self):
        raise RuntimeError("no")


@pytest.fixture
def ctx():
    c = RayContext(num_workers=2).init()
    yield c
    c.stop()


def test_remote_tasks_parallel_map(ctx):
    refs = [ctx.remote(_square, i) for i in range(20)]
    assert all(isinstance(r, ObjectRef) for r in refs)
    assert ctx.get(refs) == [i * i for i in range(20)]
    # out-of-order get works
    a, b = ctx.remote(_square, 7), ctx.remote(_square, 8)
    assert ctx.get(b) == 64 and ctx.get(a) == 49


def test_task_error_propagates(ctx):
    with pytest.raises(RayTaskError, match="intentional"):
        ctx.get(ctx.remote(_boom))
    # pool survives a failed task
    assert ctx.get(ctx.remote(_square, 3)) == 9


def test_actor_keeps_state(ctx):
    c = ctx.actor(Counter, 10)
    refs = [c.add.remote(1) for _ in range(5)]
    assert ctx.get(refs) == [11, 12, 13, 14, 15]
    assert ctx.get(c.value.remote()) == 15


def test_actor_construction_failure_is_loud(ctx):
    with pytest.raises(RayTaskError, match="construction failed"):
        ctx.actor(BadActor)


def test_uninitialized_context_raises():
    c = RayContext(2)
    with pytest.raises(RuntimeError, match="init"):
        c.remote(_square, 1)


def test_workers_die_with_parent(tmp_path):
    """JVMGuard parity: kill -9 the driver → workers must exit."""
    script = textwrap.dedent("""
        import os, sys, time
        sys.path.insert(0, %r)
        from analytics_zoo_tpu.ray import RayContext
        ctx = RayContext(2).init()
        pids = [p.pid for p in ctx._procs]
        print(" ".join(map(str, pids)), flush=True)
        time.sleep(60)
    """) % (os.getcwd(),)
    proc = subprocess.Popen([sys.executable, "-c", script],
                            stdout=subprocess.PIPE, text=True)
    pids = [int(p) for p in proc.stdout.readline().split()]
    assert pids
    os.kill(proc.pid, signal.SIGKILL)
    proc.wait()
    deadline = time.time() + 10
    while time.time() < deadline:
        alive = []
        for p in pids:
            try:
                os.kill(p, 0)
                alive.append(p)
            except OSError:
                pass
        if not alive:
            break
        time.sleep(0.3)
    assert not alive, f"orphaned workers survived driver kill: {alive}"


def test_get_twice_returns_cached_result(ctx):
    ref = ctx.remote(_square, 6)
    assert ctx.get(ref) == 36
    assert ctx.get(ref) == 36  # must not hang (ray.get semantics)


def test_unpicklable_task_fails_at_submission(ctx):
    with pytest.raises(RayTaskError, match="picklable"):
        ctx.remote(lambda: 1)


def test_crashed_worker_raises_instead_of_hanging(ctx):
    ref = ctx.remote(os._exit, 0)  # worker dies before replying
    with pytest.raises(RayTaskError, match="died"):
        ctx.get(ref)


def test_timeout_raises_timeout_error_and_is_global(ctx):
    refs = [ctx.remote(time.sleep, 5) for _ in range(4)]
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        ctx.get(refs, timeout=0.5)
    assert time.monotonic() - t0 < 2.0  # one deadline for the whole list


def test_numpy_payloads(ctx):
    a = np.arange(6).reshape(2, 3)
    ref = ctx.remote(np.dot, a, a.T)
    np.testing.assert_array_equal(ctx.get(ref), a @ a.T)


class _BoomInit:
    def __init__(self):
        raise RuntimeError("boom at init")


class _Counter2:
    def __init__(self):
        self.n = 0

    def bump(self):
        self.n += 1
        return self.n


def test_second_actor_init_failure_not_masked():
    """Actor construction acks use unique ids — a second actor's failed
    __init__ must raise immediately, not be masked by the first actor's
    cached ack (code-review regression)."""
    import pytest

    from analytics_zoo_tpu.ray import RayContext
    from analytics_zoo_tpu.ray.raycontext import RayTaskError

    ctx = RayContext(num_workers=1).init()
    try:
        ok = ctx.actor(_Counter2)
        assert ctx.get(ok.bump.remote()) == 1
        with pytest.raises(RayTaskError, match="boom at init"):
            ctx.actor(_BoomInit)
        # first actor still healthy afterwards
        assert ctx.get(ok.bump.remote()) == 2
    finally:
        ctx.stop()


class _ExitInit:
    def __init__(self):
        import os
        os._exit(7)  # dies WITHOUT sending a construction ack


def test_actor_dying_without_ack_raises_not_hangs():
    """A child that exits before acking (segfault/os._exit) must raise
    RayTaskError promptly instead of spinning forever (code-review
    regression)."""
    import time

    import pytest

    from analytics_zoo_tpu.ray import RayContext
    from analytics_zoo_tpu.ray.raycontext import RayTaskError

    ctx = RayContext(num_workers=1).init()
    try:
        t0 = time.monotonic()
        with pytest.raises(RayTaskError, match="died"):
            ctx.actor(_ExitInit)
        assert time.monotonic() - t0 < 30
    finally:
        ctx.stop()


def _jax_platform_seen_by_worker():
    import jax
    return os.environ.get("JAX_PLATFORMS"), jax.default_backend()


def test_workers_are_pinned_to_the_cpu(monkeypatch):
    """The driver holds the chip, so a worker's own environment names the
    CPU before it imports anything — whatever the driver's says — and the
    driver's variable is put back."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    c = RayContext(num_workers=1).init()
    try:
        assert c.get(c.remote(_jax_platform_seen_by_worker),
                     timeout=120) == ("cpu", "cpu")
    finally:
        c.stop()
    assert os.environ["JAX_PLATFORMS"] == "tpu,cpu"
