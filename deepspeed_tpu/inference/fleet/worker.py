"""graft-fleet replica worker: ``python -m deepspeed_tpu.inference.fleet.worker``.

One serving process in the fleet: builds an engine + continuous-batching
scheduler (``chip_smoke.serve_phase``'s construction path), then loops — requests in as
line-delimited JSON on stdin, ``done``/``tick`` out on stdout
(``protocol.py``), logs on stderr, liveness through the PR-13 heartbeat
file the scheduler touches every tick.

SIGTERM is the migrate path: refuse the queue (``refused`` messages the
router re-dispatches), export every in-flight request's KV through the
manifest+digest bundle codec, announce ``migrated_out``, exit 143. A
``MigrationError`` (sampling on, save failed) falls back to the PR-14
drain — finish in-flight locally, then exit 143. SIGKILL gets no say,
which is the point: the router's heartbeat probe + at-most-once
re-admission are what recover from it.

Env (set by :class:`SubprocessReplica` / the fleet bench):
  FLEET_MODEL=test        model family config (gpt2 families)
  FLEET_SLOTS=4           decode slots
  FLEET_CHUNK=16          prefill chunk
  FLEET_POSITIONS=128     context length
  FLEET_KV_QUANT=1        int8 KV pools
  FLEET_PREFIX_CACHE=     on|off: content-hashed KV prefix caching
                          (unset = on)
  FLEET_POOL_TOKENS=0     KV pool token budget (0 = slots x context);
                          the serve_prefix_fleet_* rungs size this
                          ABOVE slots x context so the pool has spare
                          capacity for cached prefixes
  FLEET_TICK_SLEEP_MS=0   emulated per-tick device time: on a real fleet
                          each replica owns an accelerator and the host
                          CPU idles while the tick runs on-device; the
                          1-core CPU rig has no such idle, so the
                          scaling row sleeps this long after each step
                          to reproduce the device-bound regime
  FLEET_BUNDLE_DIR=...    where a SIGTERM lands the migration bundle
  FLEET_TELEMETRY_DIR=... JSONL run dir (serve_tick etc.); unset = off
  FLEET_NAME=...          replica name (telemetry job name)
  DS_ELASTIC_HEARTBEAT_FILE=...  liveness file (parent-owned)
"""

import os
import select
import sys
import time

import numpy as np


def build_scheduler():
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler,
                                                 ServingConfig)
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    model = os.environ.get("FLEET_MODEL", "test")
    positions = int(os.environ.get("FLEET_POSITIONS", "128"))
    cfg = get_gpt2_config(model, n_positions=positions, dtype=None)
    engine = deepspeed_tpu.init_inference(GPT2LMHeadModel(cfg),
                                          replace_with_kernel_inject=True,
                                          max_out_tokens=positions)
    telemetry = None
    tdir = os.environ.get("FLEET_TELEMETRY_DIR")
    if tdir:
        from deepspeed_tpu.runtime.config import TelemetryConfig
        from deepspeed_tpu.runtime.telemetry import RuntimeTelemetry
        telemetry = RuntimeTelemetry(TelemetryConfig(
            enabled=True, output_path=tdir,
            job_name=os.environ.get("FLEET_NAME", f"replica_{os.getpid()}")))
    scfg = ServingConfig(
        slots=int(os.environ.get("FLEET_SLOTS", "4")),
        prefill_chunk=int(os.environ.get("FLEET_CHUNK", "16")),
        kv_quant=os.environ.get("FLEET_KV_QUANT", "1") == "1",
        kv_pool_tokens=int(os.environ.get("FLEET_POOL_TOKENS", "0")) or None,
        prefix_cache=os.environ.get("FLEET_PREFIX_CACHE") or "on")
    sched = ContinuousBatchingScheduler(engine, scfg, telemetry=telemetry)
    if telemetry is not None:
        # the run header carries the serving program's static price +
        # backend/scope so this replica's JSONL is a graft-calibrate fit
        # source (scope serve_decode) exactly like a training run's
        import jax
        telemetry.write_run_header(
            {"bench": "fleet_worker", "model": model, "pid": os.getpid(),
             "backend": jax.default_backend(), "scope": "serve_decode",
             # graft-calibrate separation markers: runs whose prefill is
             # partly served from the prefix cache must not pool with
             # full-prefill serve_decode samples (the field's PRESENCE is
             # what collect_samples keys its mixed-run refusal on; the
             # per-request counts land in serve_request events)
             "prefix_cache": sched.prefix_cache,
             "cached_prefix_tokens": 0},
            static_price=sched.serving_static_price())
    sched.warmup()
    return sched, telemetry


def main() -> int:
    from deepspeed_tpu.inference.fleet import protocol
    from deepspeed_tpu.inference.fleet.migrate import bundle_rids, save_bundle
    from deepspeed_tpu.inference.serving import MigrationError, Request
    from deepspeed_tpu.runtime.resilience.signals import (
        DEFAULT_PREEMPT_EXIT_CODE, PreemptionGuard)

    out = sys.stdout
    guard = PreemptionGuard().install()
    sched, telemetry = build_scheduler()
    protocol.send(out, {"type": "ready", "pid": os.getpid(),
                        "slots": sched.slots, "capacity": sched.capacity})

    stdin_fd = sys.stdin.fileno()
    os.set_blocking(stdin_fd, False)
    buf = b""
    fin_idx = 0
    stopping = False
    tick = 0
    last_idle_tick = 0.0
    last_busy_tick = 0.0
    tick_sleep = float(os.environ.get("FLEET_TICK_SLEEP_MS", "0")) / 1e3

    def drain_finished():
        nonlocal fin_idx
        while fin_idx < len(sched.finished):
            req = sched.finished[fin_idx]
            fin_idx += 1
            protocol.send(out, {"type": "done",
                                "rid": req.meta.get("fleet_rid"),
                                "output": list(req.output),
                                "stats": req.stats()})

    def read_msgs():
        nonlocal buf
        msgs = []
        while True:
            try:
                ready, _, _ = select.select([stdin_fd], [], [], 0)
            except (OSError, ValueError):
                return msgs, True
            if not ready:
                return msgs, False
            try:
                chunk = os.read(stdin_fd, 65536)
            except (BlockingIOError, OSError):
                return msgs, False
            if not chunk:  # router hung up
                return msgs, True
            buf += chunk
            while b"\n" in buf:
                line, buf = buf.split(b"\n", 1)
                msg = protocol.parse_line(line.decode("utf-8", "replace"))
                if msg is not None:
                    msgs.append(msg)

    while True:
        if guard.requested:
            signal_name = guard.consume()
            refused = sched.queue.refuse_all(f"draining on {signal_name}")
            for req in refused:
                protocol.send(out, {"type": "refused",
                                    "rid": req.meta.get("fleet_rid"),
                                    "reason": req.refuse_reason})
            if telemetry is not None:
                telemetry.emit("serve_drain", signal=signal_name,
                               in_flight=len(sched.in_flight),
                               refused=len(refused))
            if sched.in_flight:
                bundle_dir = os.environ.get(
                    "FLEET_BUNDLE_DIR", f"/tmp/fleet_bundle_{os.getpid()}")
                try:
                    payloads = sched.export_inflight(release=False)
                    save_bundle(payloads, bundle_dir)
                    sched.release_inflight()
                    protocol.send(out, {"type": "migrated_out",
                                        "bundle": bundle_dir,
                                        "rids": bundle_rids(payloads)})
                    if telemetry is not None:
                        telemetry.emit("serve_migrate_out", signal=signal_name,
                                       migrated=len(payloads),
                                       bundle=bundle_dir)
                except MigrationError as e:
                    print(f"fleet worker: migration refused ({e}) — draining",
                          file=sys.stderr, flush=True)
                    sched.run_until_drained(admit=False)
                    drain_finished()
            protocol.send(out, {"type": "bye",
                                "exit": DEFAULT_PREEMPT_EXIT_CODE})
            if telemetry is not None:
                telemetry.close()
            return DEFAULT_PREEMPT_EXIT_CODE

        msgs, eof = read_msgs()
        for msg in msgs:
            kind = msg["type"]
            if kind == "request":
                req = Request(prompt=np.asarray(msg["prompt"], np.int32),
                              max_new_tokens=msg["max_new_tokens"],
                              eos_token_id=msg.get("eos_token_id"))
                req.meta["fleet_rid"] = msg["rid"]
                sched.submit(req)
                if req.state == "refused":
                    protocol.send(out, {"type": "refused", "rid": msg["rid"],
                                        "reason": req.refuse_reason})
            elif kind == "migrate_in":
                from deepspeed_tpu.inference.fleet.migrate import receive_bundle
                try:
                    admitted, refused_p = receive_bundle(sched, msg["bundle"])
                    protocol.send(out, {
                        "type": "migrated_in",
                        "rids": [r.meta.get("fleet_rid") for r in admitted],
                        "refused_rids": bundle_rids(refused_p)})
                except MigrationError as e:
                    print(f"fleet worker: bundle refused ({e})",
                          file=sys.stderr, flush=True)
                    protocol.send(out, {"type": "migrated_in", "rids": [],
                                        "refused_rids": [],
                                        "error": str(e)})
            elif kind == "stop":
                stopping = True

        if sched.busy:
            sched.step()
            if tick_sleep:
                time.sleep(tick_sleep)
            tick += 1
            drain_finished()
            # load signals are a cadence, not a per-step obligation — a
            # tick message per step doubles the pipe traffic the router
            # must parse while the signals barely change
            now = time.monotonic()
            if now - last_busy_tick > 0.05:
                last_busy_tick = now
                protocol.send(out, {"type": "tick",
                                    "signals": sched.signals()})
        elif stopping or eof:
            protocol.send(out, {"type": "bye", "exit": 0})
            if telemetry is not None:
                telemetry.close()
            return 0
        else:
            # idle: stay alive (heartbeat) and wait for work without
            # burning a core; a cadenced tick message keeps the router's
            # load view fresh even with no requests moving
            sched._touch_serving_heartbeat(tick)
            now = time.monotonic()
            if now - last_idle_tick > 0.2:
                last_idle_tick = now
                protocol.send(out, {"type": "tick",
                                    "signals": sched.signals()})
            try:
                select.select([stdin_fd], [], [], 0.02)
            except (OSError, ValueError):
                pass


if __name__ == "__main__":
    sys.exit(main())
