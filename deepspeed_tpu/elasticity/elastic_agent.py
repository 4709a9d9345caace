"""Elastic restart supervisor (reference ``elasticity/elastic_agent.py:28``
``DSElasticAgent``).

The reference plugs into torchelastic: it watches rendezvous membership,
tears the job down when a worker dies, and relaunches training at the
surviving world size, with DeepSpeed's elasticity config guaranteeing a
valid batch configuration at every size. On TPU there is no torchelastic;
the equivalent role is a LAUNCHER-LEVEL supervisor around a single-process
SPMD job:

* liveness = process exit code + a heartbeat file the training loop
  touches (a hung accelerator backend hangs *inside* a dispatch, so
  exit-code monitoring alone never fires — heartbeat staleness is the
  TPU-shaped failure detector);
* recovery = respawn the training command at the surviving device count
  (``DS_ELASTIC_WORLD_SIZE`` env the script reads), with the elasticity
  batch math (``elasticity.compute_elastic_config``) validating the new
  size and the orbax checkpoint engine's cross-topology restore resuming
  from the last durable step.

The supervisor is deliberately command-agnostic: it runs any argv, so it
doubles as a bench/babysitter harness (a hung run gets killed and
retried).
"""

import json
import os
import signal
import subprocess
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence

from deepspeed_tpu.utils.logging import logger

HEARTBEAT_ENV = "DS_ELASTIC_HEARTBEAT_FILE"
WORLD_ENV = "DS_ELASTIC_WORLD_SIZE"
RESTART_ENV = "DS_ELASTIC_RESTART_COUNT"


_LAST_TOUCH = {}  # path -> monotonic time of last touch (cadence throttle)


def touch_heartbeat(path: Optional[str] = None, min_interval: float = 0.0,
                    payload: Optional[Dict] = None) -> None:
    """Called by the training loop (each step / each checkpoint): refreshes
    the supervisor's liveness signal. No-op when not under an agent.

    ``min_interval``: skip the filesystem touch if this path was refreshed
    less than that many seconds ago — the engine's per-step call site runs
    cadenced (``resilience.heartbeat_interval``) so liveness costs one
    write per interval, not one per step, off the hot path. Supervisors
    must size ``heartbeat_timeout`` well above the producer's interval.

    The file carries a small JSON payload (pid, monotonic clock, wall
    time, plus caller fields — the engine sends ``global_step`` and the
    last telemetry span name) so a supervisor or ``tools/fault_bench.py``
    can report *how far* a child got, not just that it was alive; mtime
    stays the liveness clock (:func:`read_heartbeat` for the payload).

    A payload-less call on an existing file refreshes the mtime ONLY: a
    supervisor's backoff sleeps and bench arm-touches share the child's
    file and must not clobber the training process's progress record."""
    path = path or os.environ.get(HEARTBEAT_ENV)
    if not path:
        return
    if min_interval > 0.0:
        now = time.monotonic()
        if now - _LAST_TOUCH.get(path, float("-inf")) < min_interval:
            return
        _LAST_TOUCH[path] = now
    if payload is None and os.path.exists(path):
        os.utime(path, None)
        return
    data = {"pid": os.getpid(), "monotonic": time.monotonic(), "time": time.time()}
    if payload:
        data.update(payload)
    try:
        blob = json.dumps(data)
    except (TypeError, ValueError):  # unserializable caller field
        blob = json.dumps({k: data[k] for k in ("pid", "monotonic", "time")})
    # atomic publish: a SIGKILL (or a supervisor read) landing mid-write
    # must never see a truncated record — the post-mortem payload is the
    # whole point of the file
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as fh:
            fh.write(blob)
        os.replace(tmp, path)
    finally:
        try:
            os.unlink(tmp)
        except OSError:
            pass
    os.utime(path, None)


def read_heartbeat(path: Optional[str] = None) -> Optional[Dict]:
    """The last heartbeat payload, or None (missing file / pre-payload
    empty file / torn write — a reader must never crash on liveness
    metadata)."""
    path = path or os.environ.get(HEARTBEAT_ENV)
    if not path:
        return None
    try:
        with open(path) as fh:
            blob = fh.read()
    except OSError:
        return None
    if not blob.strip():
        return None
    try:
        data = json.loads(blob)
    except json.JSONDecodeError:
        return None
    return data if isinstance(data, dict) else None


def heartbeat_age(path: Optional[str] = None,
                  now: Optional[float] = None) -> Optional[float]:
    """Seconds since the heartbeat file was last touched, or None when
    there is no file (never started / already reaped). Mtime is the
    liveness clock — the payload's ``monotonic`` field is the *writer's*
    clock and only comparable in-host; mtime staleness is what both the
    supervisor's hang detector and the fleet router's liveness probe
    compare against their timeout."""
    path = path or os.environ.get(HEARTBEAT_ENV)
    if not path:
        return None
    try:
        mtime = os.stat(path).st_mtime
    except OSError:
        return None
    return max(0.0, (now if now is not None else time.time()) - mtime)


class DSElasticAgent:
    """Supervise a training command; on death or heartbeat silence, restart
    it at the next world size.

    Args:
        cmd: argv of the training job. It must read ``DS_ELASTIC_WORLD_SIZE``
            (device count to train at), call :func:`touch_heartbeat`
            regularly, and resume from its checkpoint dir on start.
        world_sizes: descending ladder of world sizes to try — index
            ``restart_count`` is used (clamped to the last entry). The
            training config's elasticity block should admit each size
            (``compute_elastic_config`` raises otherwise — validate with
            :meth:`validate_world_sizes`).
        heartbeat_timeout: seconds of heartbeat silence before the child is
            declared hung and killed (the hang detector).
        max_restarts: give up after this many restarts.
        env: extra environment for the child.
        on_restart: callback ``(restart_count, world_size) -> None``.
        checkpoint_dir: the job's checkpoint dir. When set, every attempt's
            history row records the old→new topology transition — the
            stamped world size of the newest intact tag vs the attempt's
            target world — and whether the relaunch resumes plain,
            reshards (graft-elastic ``resume_elastic``), or starts fresh.
            Read from ``metadata.json`` stamps only: the supervisor never
            opens checkpoint state (and never initializes jax).
    """

    def __init__(self, cmd: Sequence[str], world_sizes: Sequence[int],
                 heartbeat_timeout: float = 60.0, max_restarts: int = 3,
                 env: Optional[dict] = None, poll_interval: float = 0.5,
                 startup_timeout: Optional[float] = None,
                 on_restart: Optional[Callable[[int, int], None]] = None,
                 checkpoint_dir: Optional[str] = None):
        assert world_sizes, "world_sizes ladder must be non-empty"
        self.cmd = list(cmd)
        self.world_sizes = list(world_sizes)
        self.checkpoint_dir = checkpoint_dir
        self.heartbeat_timeout = float(heartbeat_timeout)
        # a child cannot heartbeat until backend init + first-step compile
        # finish (minutes on a cold cache) — the staleness clock before the
        # FIRST touch uses this longer budget so a healthy-but-compiling
        # child is not declared hung and killed into a restart cascade
        self.startup_timeout = (float(startup_timeout) if startup_timeout is not None
                                else max(self.heartbeat_timeout, 1800.0))
        self.max_restarts = int(max_restarts)
        self.env = dict(env or {})
        self.poll_interval = float(poll_interval)
        self.on_restart = on_restart
        self.restart_count = 0
        self.history: List[dict] = []

    def validate_world_sizes(self, ds_config: dict) -> None:
        """Check every ladder entry admits a valid elastic batch config
        (reference: torchelastic would rendezvous into an invalid size and
        die late; here it fails before the first launch)."""
        from deepspeed_tpu.elasticity.elasticity import compute_elastic_config
        for w in self.world_sizes:
            compute_elastic_config(ds_config, world_size=w)

    def _spawn(self, world_size: int, heartbeat_path: str) -> subprocess.Popen:
        env = dict(os.environ)
        env.update(self.env)
        env[WORLD_ENV] = str(world_size)
        env[HEARTBEAT_ENV] = heartbeat_path
        env[RESTART_ENV] = str(self.restart_count)
        # drop the previous attempt's progress record so a child that dies
        # before its first touch is not credited with the old payload; the
        # fresh base record carries OUR pid, which _run filters out
        try:
            os.unlink(heartbeat_path)
        except OSError:
            pass
        touch_heartbeat(heartbeat_path)  # fresh clock for the new child
        return subprocess.Popen(self.cmd, env=env,
                                start_new_session=True)  # own group: kill cleanly

    def _kill(self, proc: subprocess.Popen) -> None:
        """Terminate a hung child and its process group. The supervisor
        kills only AFTER the heartbeat declared the child dead/hung; a chip
        belongs to one process at a time, so the child must be gone before
        the restart can claim it."""
        try:
            os.killpg(os.getpgid(proc.pid), signal.SIGTERM)
            try:
                proc.wait(timeout=10)
                return
            except subprocess.TimeoutExpired:
                os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            logger.error("elastic agent: child survived SIGKILL; abandoning it")

    def run(self, workdir: Optional[str] = None) -> int:
        """Supervise until the job exits 0, or restarts are exhausted.
        Returns the final exit code (0 on success)."""
        workdir = workdir or os.getcwd()
        # unique per-agent file: two supervisors sharing a workdir must not
        # keep each other's heartbeat fresh (masked hangs)
        heartbeat_path = os.path.join(workdir, f".ds_elastic_heartbeat.{os.getpid()}")
        try:
            return self._run(heartbeat_path)
        finally:
            try:
                os.unlink(heartbeat_path)
            except OSError:
                pass

    def _resume_decision(self, world: int) -> Optional[Dict]:
        """How this attempt will come back up (plain / reshard / fresh),
        from checkpoint metadata stamps alone. None without a
        ``checkpoint_dir``; never raises — a supervisor's bookkeeping must
        not take down a restartable job."""
        if not self.checkpoint_dir:
            return None
        try:
            from deepspeed_tpu.runtime.elastic.agent import decide_resume
            return decide_resume(self.checkpoint_dir, world)
        except Exception as e:  # noqa: BLE001 — diagnostics only
            logger.warning(f"elastic agent: cannot read checkpoint topology: {e}")
            return None

    def _run(self, heartbeat_path: str) -> int:
        prev_world: Optional[int] = None
        while True:
            idx = min(self.restart_count, len(self.world_sizes) - 1)
            world = self.world_sizes[idx]
            decision = self._resume_decision(world)
            logger.info(f"elastic agent: launching attempt {self.restart_count + 1} "
                     f"at world size {world}"
                     + (f" ({decision['resume']} resume from tag {decision['tag']}"
                        + (f", reshard {decision['ckpt_world']} -> {world}"
                           if decision["resume"] == "reshard" else "")
                        + ")" if decision else ""))
            t0 = time.time()
            proc = self._spawn(world, heartbeat_path)
            armed_mtime = os.path.getmtime(heartbeat_path)
            rc: Optional[int] = None
            reason = ""
            while True:
                rc = proc.poll()
                if rc is not None:
                    reason = f"exit rc={rc}"
                    break
                try:
                    mt = os.path.getmtime(heartbeat_path)
                except FileNotFoundError:
                    # deleted out from under us (workdir cleanup): recreate
                    # and keep supervising rather than crashing and orphaning
                    # the live child
                    touch_heartbeat(heartbeat_path)
                    armed_mtime = os.path.getmtime(heartbeat_path)
                    continue
                age = time.time() - mt
                # before the child's first touch, the mtime is still our own
                # arm-touch: apply the startup budget (backend init + cold
                # compile), not the steady-state step budget
                budget = self.startup_timeout if mt <= armed_mtime else self.heartbeat_timeout
                if age > budget:
                    phase = "startup" if mt <= armed_mtime else "heartbeat"
                    reason = f"{phase} silent {age:.1f}s (hung backend)"
                    self._kill(proc)
                    # a graceful SIGTERM handler may exit 0 — the AGENT
                    # declared this attempt dead; rc must reflect that or a
                    # 5%-done job would be reported as finished
                    rc = proc.returncode if proc.returncode not in (None, 0) else -9
                    break
                time.sleep(self.poll_interval)
            # the payload says how far the child got (global_step + last
            # telemetry span) — restart logs and post-mortems report
            # progress, not just liveness
            hb = read_heartbeat(heartbeat_path)
            if hb and hb.get("pid") == os.getpid():
                hb = None  # our own arm-touch record: the child never reported
            progress = ({k: hb[k] for k in ("global_step", "last_span", "pid",
                                            "world_size", "mesh_axes")
                         if k in hb} if hb else None)
            row = dict(world_size=world, rc=rc, reason=reason,
                       duration_s=round(time.time() - t0, 2),
                       last_heartbeat=progress)
            # old→new topology record: what this attempt resumed from and
            # how (plain / reshard / fresh) — restart logs and post-mortems
            # narrate fleet reshapes, not just exit codes. The row always
            # carries the full documented key set; without a checkpoint_dir
            # the decision fields stay None (resume mode unobservable).
            topo = dict(prev_world_size=prev_world, world_size=world,
                        resume=None, tag=None, ckpt_world=None, ckpt_axes=None)
            topo.update(decision or {})
            row["topology"] = topo
            self.history.append(row)
            prev_world = world
            if rc == 0:
                logger.info(f"elastic agent: job finished at world size {world}")
                return 0
            if self.restart_count >= self.max_restarts:
                logger.error(f"elastic agent: giving up after {self.restart_count + 1} "
                             f"attempts ({reason})")
                return rc if rc is not None else 1
            self.restart_count += 1
            next_world = self.world_sizes[min(self.restart_count, len(self.world_sizes) - 1)]
            logger.info(f"elastic agent: attempt failed ({reason}"
                        + (f"; last progress {progress}" if progress else "")
                        + f"); restarting at world size {next_world}")
            if self.on_restart is not None:
                self.on_restart(self.restart_count, next_world)

# NB: this module deliberately uses plain `logger`, never `log_dist` —
# log_dist resolves the process index, which initializes the jax backend;
# a supervisor must stay alive when the accelerator is exactly what's hung.
