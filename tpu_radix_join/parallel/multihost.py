"""Multi-process (multi-host) bootstrap: the ``MPI_Init`` analog.

The reference becomes a distributed job by being launched under ``mpirun``
(main.cpp:36-48: ``MPI_Init`` + ``Comm_size/rank`` discovery).  A multi-host
JAX job is launched as one process per host with a shared coordinator; after
``initialize()`` every process sees the whole pod through ``jax.devices()``
and the same shard_map programs run unchanged — the mesh is the cluster.

The join pipeline needs nothing else: collectives are compiled against mesh
axes, and ``make_hierarchical_mesh`` (parallel/mesh.py) lays the ``dcn`` axis
along process boundaries so the shuffle's bulk hops ride ICI.

This module is environment-driven and single-host-safe: with no cluster
variables set it is a no-op, so every entry point can call it unconditionally
(the way every reference binary calls ``MPI_Init``).

Resilience (the hardening ``MPI_Init`` never had): the coordinator connect
runs under a ``robustness.retry.RetryPolicy`` — a worker that races ahead of
a slow coordinator backs off and retries instead of dying, and a worker that
can never connect fails with the ``coordinator_timeout`` failure class after
a bounded schedule rather than hanging the job.  Knobs come from the
environment (``TPU_RJ_COORD_ATTEMPTS``, ``TPU_RJ_COORD_BACKOFF_S``,
``TPU_RJ_COORD_TIMEOUT_S``) or an explicit ``retry_policy``.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Optional

import jax

from tpu_radix_join.robustness import faults as _faults
from tpu_radix_join.robustness.retry import (COORDINATOR_TIMEOUT,
                                             RetriesExhausted, RetryPolicy,
                                             execute)

_initialized = False


class CoordinatorTimeout(ConnectionError):
    """Could not reach the distributed coordinator within policy.

    Carries the retry history the terminal re-raise used to lose:
    ``attempts`` (connect attempts made) and ``backoff_s`` (cumulative
    seconds slept between them) — rendered into the message and picked
    up by the forensics bundle (``bundle_extra``), so a post-mortem
    distinguishes "died on the first dial" from "backed off for a minute
    against a coordinator that never answered"."""

    failure_class = COORDINATOR_TIMEOUT

    def __init__(self, msg: str, attempts: int = 1, backoff_s: float = 0.0):
        super().__init__(msg)
        self.attempts = attempts
        self.backoff_s = backoff_s
        #: merged into the post-mortem bundle's ``extra`` by the failure
        #: path (main._emit_failure_bundle)
        self.bundle_extra = {"coordinator_attempts": attempts,
                             "coordinator_backoff_s": round(backoff_s, 3)}


def _default_policy() -> RetryPolicy:
    return RetryPolicy(
        max_attempts=int(os.environ.get("TPU_RJ_COORD_ATTEMPTS", "3")),
        base_delay_s=float(os.environ.get("TPU_RJ_COORD_BACKOFF_S", "1.0")),
        multiplier=2.0,
        max_delay_s=30.0,
        jitter=0.1,
        # per-process seed: ranks de-synchronize their retry storms
        seed=int(os.environ.get("JAX_PROCESS_ID", "0")),
    )


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               retry_policy: Optional[RetryPolicy] = None,
               connect_timeout_s: Optional[float] = None,
               measurements=None,
               _sleep: Optional[Callable[[float], None]] = None) -> bool:
    """Join the multi-process world if one is configured; returns True when
    running distributed.

    Joining is strictly opt-in: it happens only with an explicit
    ``coordinator_address`` argument or ``JAX_COORDINATOR_ADDRESS`` in the
    environment (plus ``JAX_NUM_PROCESSES``/``JAX_PROCESS_ID`` — the moral
    equivalent of mpirun's rank environment).  Cloud TPU pod launchers that
    rely on jax's own pod auto-detection should call
    ``jax.distributed.initialize()`` directly before importing this package;
    auto-detection is deliberately not replicated here: a single-host
    run must never block looking for a metadata server.

    ``connect_timeout_s`` bounds each connect attempt (forwarded to
    ``jax.distributed.initialize(initialization_timeout=...)`` where the
    installed jax supports it, default
    ``TPU_RJ_COORD_TIMEOUT_S``); retryable connect failures (timeout /
    connection errors / the injectable ``multihost.coordinator_connect``
    fault) back off per ``retry_policy`` and terminally raise
    :class:`CoordinatorTimeout`.  ``_sleep`` is test-injectable.
    """
    global _initialized
    if _initialized or jax.distributed.is_initialized():
        return jax.process_count() > 1
    env = os.environ
    coordinator_address = coordinator_address or env.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None and "JAX_NUM_PROCESSES" in env:
        num_processes = int(env["JAX_NUM_PROCESSES"])
    if process_id is None and "JAX_PROCESS_ID" in env:
        process_id = int(env["JAX_PROCESS_ID"])
    if coordinator_address is None:
        return False   # single-process run; nothing to join
    if connect_timeout_s is None and "TPU_RJ_COORD_TIMEOUT_S" in env:
        connect_timeout_s = float(env["TPU_RJ_COORD_TIMEOUT_S"])

    kwargs = dict(coordinator_address=coordinator_address,
                  num_processes=num_processes, process_id=process_id)
    if connect_timeout_s is not None:
        kwargs["initialization_timeout"] = int(connect_timeout_s)

    def connect():
        _faults.check(_faults.COORD_CONNECT, measurements)
        try:
            jax.distributed.initialize(**kwargs)
        except TypeError:
            # older jax.distributed.initialize without initialization_timeout
            kwargs.pop("initialization_timeout", None)
            jax.distributed.initialize(**kwargs)

    policy = retry_policy or _default_policy()
    try:
        execute(connect, policy,
                retryable=(ConnectionError, TimeoutError,
                           _faults.InjectedFault, RuntimeError),
                sleep=_sleep or time.sleep,
                measurements=measurements,
                label="coordinator_connect")
    except RetriesExhausted as e:
        # the slept schedule is one delay per attempt pair actually made
        backoff_s = sum(policy.schedule()[:max(0, e.attempts - 1)])
        raise CoordinatorTimeout(
            f"could not reach coordinator {coordinator_address} after "
            f"{e.attempts} attempt(s) ({backoff_s:.1f}s cumulative "
            f"backoff): {e.last_error!r}",
            attempts=e.attempts, backoff_s=backoff_s) from e
    _initialized = True
    return jax.process_count() > 1


def process_info() -> tuple[int, int]:
    """(process_id, process_count) — the ``Comm_rank``/``Comm_size`` pair."""
    return jax.process_index(), jax.process_count()
