"""Config/context tests — layered config merge and multi-word key
canonicalization (round-1 ADVICE #3: ``ZOO_TPU_FAILURE_RETRY_TIMES`` and
``init_zoo_context(failure_retry_times=...)`` must land on
``zoo.failure.retry_times``)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from analytics_zoo_tpu.common.context import (get_zoo_context,
                                              init_zoo_context,
                                              reset_zoo_context)


def test_kwargs_override_multiword_leaf_key():
    ctx = init_zoo_context(failure_retry_times=3)
    assert ctx.get("zoo.failure.retry_times") == 3


def test_kwargs_override_retry_window():
    ctx = init_zoo_context(failure_retry_window_sec=120)
    assert ctx.get("zoo.failure.retry_window_sec") == 120


def test_env_override_multiword_leaf_key(monkeypatch):
    monkeypatch.setenv("ZOO_TPU_FAILURE_RETRY_TIMES", "7")
    reset_zoo_context()
    ctx = init_zoo_context()
    assert ctx.get("zoo.failure.retry_times") == 7


def test_env_override_namespaced_key(monkeypatch):
    monkeypatch.setenv("ZOO_TPU_MESH_MODEL", "1")
    reset_zoo_context()
    ctx = init_zoo_context()
    assert ctx.get("zoo.mesh.model") == 1


def test_unknown_key_falls_back_to_dots():
    ctx = init_zoo_context(custom_flag=True)
    assert ctx.get("zoo.custom.flag") is True


@pytest.mark.parametrize("kwarg", [{"train_scan_steps": 4},
                                   {"train_device_cache": True},
                                   {"train_fuse_epochs": 3}])
def test_retired_dispatch_keys_raise_by_kwarg(kwarg):
    """A key that selected a deleted dispatch path raises, naming itself:
    accepted in silence like any unknown key, it would cost the job the
    path it asked for without a word."""
    key = "zoo.train." + next(iter(kwarg))[len("train_"):]
    with pytest.raises(ValueError, match=key.replace(".", r"\.")):
        init_zoo_context(**kwarg)
    # the rejected call left no context behind: the next one is built anew
    assert key not in get_zoo_context().conf


def test_retired_key_raises_by_env(monkeypatch):
    monkeypatch.setenv("ZOO_TPU_TRAIN_DEVICE_CACHE", "1")
    reset_zoo_context()
    with pytest.raises(ValueError, match=r"zoo\.train\.device_cache.*one "
                                         r"optimizer step at a time"):
        init_zoo_context()


def test_retired_key_raises_by_conf_dict():
    with pytest.raises(ValueError, match=r"zoo\.train\.fuse_epochs"):
        init_zoo_context(conf={"zoo.train.fuse_epochs": 3})


def test_conf_dict_highest_besides_kwargs():
    ctx = init_zoo_context(conf={"zoo.seed": 123})
    assert ctx.seed == 123


def test_context_idempotent():
    a = init_zoo_context()
    b = get_zoo_context()
    assert a is b


def test_compute_dtype_policy_wired():
    """zoo.compute.dtype drives the engine precision policy (it was once a
    documented-but-dead conf key)."""
    import jax.numpy as jnp
    import pytest

    from analytics_zoo_tpu.common import init_zoo_context
    from analytics_zoo_tpu.common.context import reset_zoo_context
    from analytics_zoo_tpu.pipeline.api.keras.engine import compute_dtype

    init_zoo_context(compute_dtype="bfloat16")
    assert compute_dtype() == jnp.bfloat16
    reset_zoo_context()
    init_zoo_context()
    assert compute_dtype() == jnp.float32
    reset_zoo_context()
    with pytest.raises(ValueError, match="float32|bfloat16"):
        init_zoo_context(compute_dtype="float16")


def test_lazy_init_does_not_clobber_manual_policy():
    """A direct set_policy() call must survive the lazy default
    init_zoo_context() that fit() triggers (code-review regression)."""
    import jax.numpy as jnp

    from analytics_zoo_tpu.common import init_zoo_context
    from analytics_zoo_tpu.pipeline.api.keras.engine import (compute_dtype,
                                                             set_policy)

    set_policy(compute_dtype=jnp.bfloat16)
    init_zoo_context()  # lazy default init — no explicit compute_dtype
    assert compute_dtype() == jnp.bfloat16
    set_policy()


def test_reinit_resets_policy_to_conf_default():
    """An explicit re-init restarts the compute policy from the merged conf
    like every other key — no stale bf16 leaking past a re-init."""
    import jax.numpy as jnp

    from analytics_zoo_tpu.common import init_zoo_context
    from analytics_zoo_tpu.common.context import reset_zoo_context
    from analytics_zoo_tpu.pipeline.api.keras.engine import compute_dtype

    init_zoo_context(compute_dtype="bfloat16")
    assert compute_dtype() == jnp.bfloat16
    init_zoo_context(seed=7)  # explicit re-init, dtype not given
    assert compute_dtype() == jnp.float32
    reset_zoo_context()
    # dtype objects are accepted like the old direct set_policy was
    init_zoo_context(compute_dtype=jnp.bfloat16)
    assert compute_dtype() == jnp.bfloat16


def test_direct_set_policy_owns_across_reinit():
    """engine.set_policy after an explicit-dtype init takes ownership: a
    later unrelated re-init must not clobber it (code-review regression)."""
    import jax.numpy as jnp

    from analytics_zoo_tpu.common import init_zoo_context
    from analytics_zoo_tpu.pipeline.api.keras.engine import (compute_dtype,
                                                             set_policy)

    init_zoo_context(compute_dtype="bfloat16")
    set_policy(compute_dtype=jnp.float32)       # user's direct override
    init_zoo_context(seed=11)                   # unrelated re-init
    assert compute_dtype() == jnp.float32


# ---------------------------------------------------------------------------
# compile-cache placement (process-global jax config: subprocesses)
# ---------------------------------------------------------------------------

_CACHE_PROBE = """
import json, os, sys
import jax
from analytics_zoo_tpu.common import context
updates = []
real_update = jax.config.update
def spy(name, value):
    updates.append(name)
    return real_update(name, value)
jax.config.update = spy
context.init_zoo_context()
print(json.dumps({"pid": os.getpid(),
                  "dir": jax.config.jax_compilation_cache_dir,
                  "fixed": context.COMPILE_CACHE_DIR,
                  "code_set_it": "jax_compilation_cache_dir" in updates}))
"""


def _cache_probe(env_dir=None):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_compile_cache_env_dir_is_left_to_jax(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the program's code sets nothing
    and jax's own handling of the variable decides."""
    got = _cache_probe(str(tmp_path / "placed"))
    assert got["dir"] == str(tmp_path / "placed")
    assert got["code_set_it"] is False


def test_compile_cache_default_is_one_fixed_path_in_the_checkout():
    """Unset, the directory is <checkout>/.jax_cache — the same in two
    processes (different pids), never a temporary or per-process name."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    a, b = _cache_probe(), _cache_probe()
    assert a["pid"] != b["pid"]
    assert a["dir"] == b["dir"] == a["fixed"] == os.path.join(repo,
                                                              ".jax_cache")
    assert a["code_set_it"] is True
