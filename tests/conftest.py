"""Test configuration: force an 8-device virtual CPU platform.

This is the TPU-world analog of a fake distributed backend (SURVEY.md §4):
all sharding/collective tests run on 8 virtual CPU devices via
``--xla_force_host_platform_device_count``. ``JAX_PLATFORMS=cpu`` is put in
the environment too, so the worker processes some tests spawn inherit it
(a fleet worker runs where its environment says).

Set ``RUN_TPU_TESTS=1`` to SKIP the CPU forcing and run on the real chip
instead — this enables the ``needs_tpu`` pallas-kernel cases
(test_fused_attention.py, test_decode_step.py, test_attention_impls.py)
that skip on the virtual CPU mesh (chip_smoke.py runs the same cases in its
kernels phase):

  RUN_TPU_TESTS=1 python -m pytest tests/test_fused_attention.py -q
"""

import os
import tempfile

RUN_ON_TPU = os.environ.get("RUN_TPU_TESTS") == "1"

if not RUN_ON_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags += " --xla_force_host_platform_device_count=8"
    if "xla_cpu_enable_concurrency_optimized_scheduler" not in flags:
        # XLA:CPU's concurrency-optimized scheduler lets replicas enter
        # independent collectives in different orders; a dp x sp program
        # (fsdp all-gathers over data + ring ppermute over seq) then
        # deadlocks its rendezvous and the runtime aborts the interpreter.
        # On jax 0.9.0 test_sp_with_fsdp_params died that way in 5 runs of
        # 6 (0 of 6 with the scheduler off), taking its xdist worker — and
        # twice the whole suite — with it.
        flags += " --xla_cpu_enable_concurrency_optimized_scheduler=false"
    os.environ["XLA_FLAGS"] = flags.strip()

import jax  # noqa: E402

# The persistent compilation cache stays OFF for this suite: main.main now
# places one by default (obs/compile.configure_compile_cache), and tests
# must not share state across runs or xdist workers. (Re-tested on jax
# 0.9.0: cache-deserialized donated programs keep their alias bytes and
# execute correctly on the CPU, but the XLA:CPU loader logs a screenful of
# "machine type doesn't match ... could lead to SIGILL" per program.)
jax.config.update("jax_enable_compilation_cache", False)

import contextlib  # noqa: E402

import pytest  # noqa: E402


def pytest_runtest_setup(item):
    """``needs_tpu`` cases skip when the test RUNS off-chip — never at
    import or collection, which every xdist worker does for every file."""
    if (item.get_closest_marker("needs_tpu")
            and jax.default_backend() != "tpu"):
        pytest.skip("pallas TPU kernel: needs the real chip "
                    "(RUN_TPU_TESTS=1, or chip_smoke.py)")


@contextlib.contextmanager
def distributed_spawn_lock():
    """Cross-xdist-worker file lock for tests that spawn their own
    jax.distributed process groups: two groups forming concurrently can
    race on coordinator ports (observed as Gloo 'connected to N peer
    ranks' failures when the 2-proc and 4-proc tests overlapped under
    ``-n 4``). Serializing group formation removes the race; the lock is
    a no-op when the suite runs single-process."""
    import fcntl

    path = os.path.join(tempfile.gettempdir(), "bllm_dist_spawn.lock")
    with open(path, "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(f, fcntl.LOCK_UN)


@pytest.fixture(autouse=True)
def _scrub_stale_ckpt_staging():
    """Remove checkpoint staging dirs (model_pg_*.tmp/.old) a crashed or
    interrupted test left in the working tree, so one test's aborted save
    can never feed a later test's auto-resume discovery."""
    yield
    import glob
    import shutil

    for root in (os.getcwd(), os.path.join(os.getcwd(),
                                           "model_checkpoints")):
        for suffix in (".tmp", ".old"):
            for d in glob.glob(os.path.join(root, f"model_pg_*{suffix}")):
                if os.path.isdir(d):
                    shutil.rmtree(d, ignore_errors=True)


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session", autouse=True)
def _assert_backend():
    if RUN_ON_TPU:
        assert jax.default_backend() == "tpu"
    else:
        assert jax.default_backend() == "cpu"
        assert len(jax.devices()) == 8
    yield
