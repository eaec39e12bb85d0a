"""Multi-process launch: jax.distributed init + localhost CI emulation.

Two ways into the same code path:

  * **Real multi-host**: every host runs the same program;
    ``initialize()`` reads the coordinator address / process id / process
    count from the ``REPRO_COORDINATOR`` / ``REPRO_PROCESS_ID`` /
    ``REPRO_NUM_PROCESSES`` environment (or explicit arguments) and calls
    ``jax.distributed.initialize``.  After that, ``jax.devices()`` is
    global and ``global_mesh()`` spans every process.

  * **CI emulation**: ``spawn_emulated(n, argv)`` launches n localhost
    subprocesses of the *same* worker program with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` (the
    HomebrewNLP/olmax run.sh idiom) and a free-port coordinator, so the
    2-process integration tests and the speedup-vs-ranks bench exercise
    the identical initialize/mesh/shard_map path a real fleet uses.

CPU processes talk through the gloo collectives backend; that config
must land before the first collective compiles, so ``initialize()`` sets
it right before ``jax.distributed.initialize``.  Like launch.mesh,
everything here is functions -- importing this module never touches jax
device state.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

from repro.faults.retry import Backoff
from repro.launch.runtime_env import runtime_env

ENV_COORDINATOR = "REPRO_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_PROCESS_ID"

# A fleet that died because the coordinator could not bind its probed
# port (the free_port() bind-then-release race: another process grabbed
# it first) is retried with a fresh port; any other failure is real and
# returned to the caller untouched.
_BIND_FAILURE_MARKERS = ("address already in use", "eaddrinuse",
                         "errno: 98", "failed to bind")


@dataclass(frozen=True)
class DistributedConfig:
    """Where this process sits in the fleet (1-process == no fleet)."""

    coordinator: str = "localhost:0"
    num_processes: int = 1
    process_id: int = 0


def env_config(environ: Optional[Dict[str, str]] = None
               ) -> Optional[DistributedConfig]:
    """Fleet coordinates from the environment; None when not launched as
    part of one (plain single-process runs stay untouched)."""
    env = os.environ if environ is None else environ
    if ENV_NUM_PROCESSES not in env:
        return None
    return DistributedConfig(
        coordinator=env.get(ENV_COORDINATOR, "localhost:0"),
        num_processes=int(env[ENV_NUM_PROCESSES]),
        process_id=int(env.get(ENV_PROCESS_ID, "0")))


def initialize(cfg: Optional[DistributedConfig] = None, *,
               collectives: str = "gloo") -> DistributedConfig:
    """Join the fleet (idempotent for 1-process configs).

    Must run before any other jax device use.  Returns the resolved
    config so workers can log their coordinates.
    """
    if cfg is None:
        cfg = env_config() or DistributedConfig()
    if cfg.num_processes > 1:
        import jax
        # CPU processes need a cross-process collectives backend; the
        # config has to land before distributed init spins up the client.
        jax.config.update("jax_cpu_collectives_implementation", collectives)
        jax.distributed.initialize(coordinator_address=cfg.coordinator,
                                   num_processes=cfg.num_processes,
                                   process_id=cfg.process_id)
    return cfg


def global_mesh(axis: str = "data"):
    """1-D mesh over every device in the fleet.  With
    ``jax.distributed.initialize`` done, ``jax.devices()`` enumerates all
    processes' devices (process 0's first, each process contiguous), so
    shard i of an evenly split axis is addressable exactly on the process
    that owns device i -- the contiguous-ownership layout the per-host
    writer tier relies on."""
    import jax
    import numpy as np
    return jax.sharding.Mesh(np.asarray(jax.devices()), (axis,))


def process_rank() -> int:
    import jax
    return jax.process_index()


def process_count() -> int:
    import jax
    return jax.process_count()


def free_port() -> int:
    """A currently free TCP port for the emulated coordinator (the usual
    bind-to-0 trick; the tiny race against other processes is fine for
    CI-scope launches)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def rank_env(rank: int, num_processes: int, coordinator: str, *,
             devices_per_process: int = 1,
             base: Optional[Dict[str, str]] = None,
             preset: bool = True) -> Dict[str, str]:
    """Child environment for emulated rank `rank`: fleet coordinates plus
    the runtime preset (tcmalloc / log level / XLA host-device flag).

    Emulated ranks are CPU processes by design (gloo collectives), so the
    child is pinned to ``JAX_PLATFORMS=cpu``: on an accelerator host it
    must never try to take a chip that its parent may hold."""
    env = (runtime_env(base, host_device_count=devices_per_process)
           if preset else dict(os.environ if base is None else base))
    env["JAX_PLATFORMS"] = "cpu"
    if not preset and devices_per_process != 1:
        from repro.launch.runtime_env import merge_xla_flags
        env["XLA_FLAGS"] = merge_xla_flags(
            env.get("XLA_FLAGS"),
            [f"--xla_force_host_platform_device_count="
             f"{devices_per_process}"])
    env[ENV_COORDINATOR] = coordinator
    env[ENV_NUM_PROCESSES] = str(num_processes)
    env[ENV_PROCESS_ID] = str(rank)
    return env


def _coordinator_bind_failed(results: List[subprocess.CompletedProcess]
                             ) -> bool:
    """Did this fleet die on the coordinator-port bind race?  Only a
    failing rank whose stderr carries a bind-failure marker counts --
    worker crashes, injected faults and timeouts are NOT retried."""
    for r in results:
        if r.returncode == 0:
            continue
        text = (r.stderr or "").lower()
        if any(m in text for m in _BIND_FAILURE_MARKERS):
            return True
    return False


def _spawn_once(num_processes: int, argv: Sequence[str], coordinator: str,
                devices_per_process: int,
                base_env: Optional[Dict[str, str]], preset: bool,
                timeout: float) -> List[subprocess.CompletedProcess]:
    procs = []
    for rank in range(num_processes):
        env = rank_env(rank, num_processes, coordinator,
                       devices_per_process=devices_per_process,
                       base=base_env, preset=preset)
        procs.append(subprocess.Popen(
            [sys.executable, *argv], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    deadline = time.monotonic() + timeout
    results: List[subprocess.CompletedProcess] = []
    for rank, proc in enumerate(procs):
        left = max(deadline - time.monotonic(), 0.1)
        try:
            out, err = proc.communicate(timeout=left)
        except subprocess.TimeoutExpired:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            out, err = proc.communicate()
        results.append(subprocess.CompletedProcess(
            proc.args, proc.returncode, out, err))
    return results


def spawn_emulated(num_processes: int, argv: Sequence[str], *,
                   devices_per_process: int = 1,
                   base_env: Optional[Dict[str, str]] = None,
                   preset: bool = True,
                   timeout: float = 600.0,
                   bind_attempts: int = 3
                   ) -> List[subprocess.CompletedProcess]:
    """Launch ``python <argv...>`` num_processes times on localhost with a
    shared free-port coordinator; wait for all; return per-rank results
    (rank order).  Does not raise on nonzero exits -- crash-tolerance
    tests inspect returncodes; use ``check_spawned`` for the common
    all-must-succeed case.

    The coordinator port comes from ``free_port()``'s bind-then-release
    probe, which races other processes on the host: by the time the fleet
    binds it, someone else may own it.  When a failing rank's stderr
    shows a bind failure, the *whole fleet* is relaunched with a fresh
    port -- up to ``bind_attempts`` total attempts with jittered backoff
    (``repro.faults.retry.Backoff``) -- since a half-initialized fleet
    can never recover in place.
    """
    results: List[subprocess.CompletedProcess] = []
    delays = Backoff(attempts=max(1, bind_attempts) - 1, base=0.1).delays()
    for attempt in range(max(1, bind_attempts)):
        coordinator = f"localhost:{free_port()}"
        results = _spawn_once(num_processes, argv, coordinator,
                              devices_per_process, base_env, preset, timeout)
        if not _coordinator_bind_failed(results):
            break
        try:
            time.sleep(next(delays))
        except StopIteration:  # attempts exhausted: return the last fleet
            break
    return results


def check_spawned(results: List[subprocess.CompletedProcess]) -> None:
    """Raise with the first failing rank's output attached."""
    for rank, r in enumerate(results):
        if r.returncode != 0:
            raise RuntimeError(
                f"emulated rank {rank} exited {r.returncode}\n"
                f"--- stdout ---\n{r.stdout}\n--- stderr ---\n{r.stderr}")


__all__ = ["DistributedConfig", "env_config", "initialize", "global_mesh",
           "process_rank", "process_count", "free_port", "rank_env",
           "spawn_emulated", "check_spawned",
           "ENV_COORDINATOR", "ENV_NUM_PROCESSES", "ENV_PROCESS_ID"]
