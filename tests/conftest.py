"""Test configuration.

- JAX runs on a virtual 8-device CPU mesh (multi-chip sharding tests without
  TPU hardware), set BEFORE any jax import.
- Mock-TPU-host fixtures mirror the reference's tests/accelerators/test_tpu.py
  pattern: TPU topology simulated via env vars, no hardware needed.
"""

import os

# XLA flags for the CPU lanes, added to whatever is set:
#  - 8 virtual devices;
#  - the plain HLO scheduler.  XLA:CPU's default one orders a program for
#    concurrency, and the virtual devices (threads of this one process) may
#    then enter two INDEPENDENT collectives in different orders: each blocks
#    until all its participants arrive, none ever does, and after 40 s the
#    rendezvous ends the whole process with LOG(FATAL) (SIGABRT, rc 134).
#    That was the order-dependent abort in test_overlap_grad_sync.py (many
#    small per-bucket collectives), 3 of 3 runs after
#    test_collective_compression.py in one process and 0 of 4 with this
#    flag.  It is a property of the in-process CPU communicator, not of the
#    step: a TPU runs a program's collectives in one fixed order.
for _flag in ("--xla_force_host_platform_device_count=8",
              "--xla_cpu_enable_concurrency_optimized_scheduler=false"):
    if _flag.split("=")[0] not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()
# The tests run on the CPU, here and on a machine with a chip alike: this
# process and every worker it starts inherit the variable, and jax honours
# it by itself.  ASSIGNED, not setdefault: a chip machine's ambient
# JAX_PLATFORMS names its TPU.  (The chip is driven by chip_smoke.py.)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("RAY_TPU_DISABLE_METADATA_SERVER", "1")
os.environ.setdefault("RAY_TPU_WORKER_QUIET", "1")
# starved 1-CPU CI host: a jit compile in one worker can stall peers'
# replies for tens of seconds; production keeps the 30s default
os.environ.setdefault("RAY_TPU_gcs_rpc_timeout_s", "90")

import jax

jax.config.update("jax_num_cpu_devices", 8)

import pytest

# ---------------------------------------------------------------------------
# Whole-session dead-man's switch: a C-level faulthandler watchdog thread
# dumps EVERY thread's stack to stderr if no progress for 10 minutes.
# Unlike the per-test SIGALRM below, this fires even when the main thread
# cannot run Python signal handlers (GIL-independent, covers the inter-test
# gaps pytest runs outside any item protocol — the round-4 investigation
# caught a silent futex hang exactly there, with alarm unset and no signal
# deliverable). repeat=True re-arms so a wedged lane leaves periodic
# evidence instead of a blank log.
# ---------------------------------------------------------------------------

import faulthandler as _fh

_fh.dump_traceback_later(600, repeat=True, exit=False)


@pytest.hookimpl(hookwrapper=True, trylast=True)
def pytest_runtest_makereport(item, call):
    # progress heartbeat: every completed phase re-arms the dead-man's
    # switch, so it only fires after 10 min of NO lane progress at all
    _fh.dump_traceback_later(600, repeat=True, exit=False)
    yield


# ---------------------------------------------------------------------------
# Per-test watchdog (no pytest-timeout in the image): SIGALRM covers the whole
# runtest protocol — fixtures included, where the one observed core-lane hang
# class lives — dumping ALL thread stacks before failing the test, so a hang
# leaves evidence instead of a silent dead lane.
# ---------------------------------------------------------------------------

_DEFAULT_TIMEOUT_S = 60
_SLOW_TIMEOUT_S = 900


class _TestTimeout(BaseException):
    # BaseException (like KeyboardInterrupt): the codebase under test is full
    # of `except Exception` retry loops that would otherwise swallow the
    # one-shot watchdog raise and leave the lane hung again
    pass


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    import faulthandler
    import signal
    import sys

    timeout = _DEFAULT_TIMEOUT_S
    if item.get_closest_marker("slow") or item.get_closest_marker("stress"):
        timeout = _SLOW_TIMEOUT_S
    m = item.get_closest_marker("timeout")
    if m is not None and (m.args or m.kwargs):
        timeout = int(m.args[0] if m.args
                      else m.kwargs.get("seconds", m.kwargs.get("timeout", timeout)))

    def _on_alarm(signum, frame):
        sys.stderr.write(f"\n=== watchdog: {item.nodeid} exceeded {timeout}s; "
                         "all thread stacks follow ===\n")
        faulthandler.dump_traceback(file=sys.stderr)
        raise _TestTimeout(f"{item.nodeid} exceeded per-test timeout of {timeout}s")

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(timeout)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def ray_start_regular():
    """Single-node cluster with a driver attached (reference: conftest.py:589)."""
    import ray_tpu

    w = ray_tpu.init(num_cpus=4)
    yield w
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Factory for multi-node clusters (reference: conftest.py:679)."""
    from ray_tpu.cluster_utils import Cluster

    clusters = []

    def factory(**kwargs):
        c = Cluster(**kwargs)
        clusters.append(c)
        return c

    yield factory
    for c in clusters:
        c.shutdown()


@pytest.fixture
def mock_tpu_host(monkeypatch):
    """Simulate one v5p host with 4 chips (reference: tests/accelerators/test_tpu.py)."""
    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "4")
    monkeypatch.setenv("TPU_NAME", "test-slice-0")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5p-8")
    monkeypatch.setenv("TPU_TOPOLOGY", "2x2x1")
    yield


@pytest.fixture(scope="session")
def greedy_reference():
    """The serving engine's reference: greedy argmax over a full re-run of
    ``llama.forward`` (the trainer's path: no cache, no paging, no chunks)
    a token at a time.  ``run(cfg, params, prompts, n_new)`` returns each
    prompt's ``n_new`` tokens.  Prompts are padded to one length so the
    forward compiles once; causal attention keeps a row's logit at its
    last real position independent of the padding behind it.  Token
    equality with the engine holds for float32 fixtures."""
    import functools

    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama

    def run(cfg, params, prompts, n_new):
        fwd = jax.jit(functools.partial(llama.forward, cfg))
        lens = np.array([len(p) for p in prompts])
        width = -(-(int(lens.max()) + n_new) // 32) * 32
        seqs = np.zeros((len(prompts), width), np.int32)
        for row, prompt in zip(seqs, prompts):
            row[:len(prompt)] = prompt
        rows = np.arange(len(prompts))
        for _ in range(n_new):
            logits = fwd(params, jnp.asarray(seqs))
            seqs[rows, lens] = np.asarray(
                jnp.argmax(logits[rows, lens - 1], axis=-1))
            lens = lens + 1
        return [seqs[i, len(p):len(p) + n_new].tolist()
                for i, p in enumerate(prompts)]

    return run
