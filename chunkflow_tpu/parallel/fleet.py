"""Elastic, preemption-native fleet supervisor.

The paper's headline capability — 18 PB produced on 3600 cloud nodes in
three regions — is an *elasticity* story: workers are cheap, preemptible
and constantly dying, and the system converges because something keeps
replacing them and the queue protocol keeps their work safe. PRs 3–6
built every input (per-phase stall shares, queue depth / receive
counts, lease state, ledger resume, per-worker ``/healthz`` +
``/metrics``); this module is the component that finally *acts* on
those signals:

* **Spawn + monitor**: each worker is a real subprocess running the
  supervised ``fetch-task-from-queue`` loop (parallel/lifecycle.py)
  with its own ``--metrics-port`` exporter; the supervisor probes
  ``/healthz`` every decision tick and scrapes ``/metrics`` for the
  dominant-stall phase and memory gauges (``restapi.scrape_worker``).
* **Scale from telemetry**: queue ``stats()`` (pending/inflight/dead),
  the fleet's dominant stall phase, and the dead-letter rate drive the
  controller — a deep, compute-bound queue adds a worker per tick up to
  ``max_workers``; a storage-bound fleet holds (more workers would just
  thrash the volume store); a sustained-idle queue drains back to
  ``min_workers``; every scale-up is gated by a host-memory watermark.
* **Preemptible by default**: a worker that misses ``probe_misses``
  consecutive health probes is quarantined — SIGKILLed, and the lease
  handles it last reported over ``/healthz`` are force-nacked
  (``QueueBase.force_release``) so other workers pick up its tasks
  *now* instead of after the visibility timeout. Scale-down is a
  graceful drain: SIGTERM → the worker's preemption handler nacks its
  in-flight task and flushes writes (``install_preemption_handler``) →
  exit 143; a drain that overstays ``term_grace`` is hard-killed. A
  seeded **spot-drill** mode (``drill_rate``) randomly reclaims live
  workers through the same SIGTERM path to prove preemption-recovery
  continuously, the way the paper's fleet lives it.
* **Crash-shaped chaos**: unexpected deaths (SIGKILL, OOM,
  ``testing/chaos.py action=kill``) are detected by reaping, their
  leases force-nacked, and replacements spawned; a crash *loop*
  (``crash_limit`` deaths inside ``crash_window``) backs respawning off
  instead of burning the host.
* **Drain-session workers**: the scheduler pipeline flushes its
  buffered tail when the fetch generator finishes, so a worker that
  long-polls an empty queue would hold its last ``async-depth`` tasks
  claimed-but-unacked (leases dutifully renewed!) for the whole poll
  budget — the fleet would look busy forever. Fleet workers therefore
  run bounded sessions: a moderate ``--retry-times`` (× a small
  ``--poll-interval``) makes an idle worker flush, ack and exit 0, and
  the supervisor — which treats exit 0 as a completion, not a death —
  respawns a fresh session while it still owes the target size. During
  an active volume the queue is rarely empty, so sessions are long; the
  churn only appears at the idle tail, where the idle-drain policy is
  about to shrink the fleet anyway.
* **Operable**: ``chunkflow fleet-run`` drives it from the CLI,
  ``fleet/*`` counters/gauges/events flow into log-summary, Prometheus
  and CloudWatch like every other subsystem, a JSON state file feeds
  ``fleet-status`` (last-seen times and exit codes for dead workers),
  and ``CHUNKFLOW_FLEET=0`` is the kill switch: a static-size fleet
  that bypasses the controller entirely while keeping
  replace-the-dead liveness.
* **One process per chip**: a TPU chip belongs to one process, and a
  worker is given no chip of its own — the first worker to start takes
  every chip of the host, and each later one dies at backend start-up
  ("The TPU is already in use by process with pid N", measured on a
  v5e with jax 0.9.0 / libtpu 0.0.34). So the supervisor refuses to
  run more than one chip-using worker on a TPU host
  (:func:`host_tpu_chips`); one worker drives all of a host's chips
  through ``inference --mesh data=N``.

See docs/fault_tolerance.md "Running a fleet" for the runbook.
"""
from __future__ import annotations

import glob
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

from chunkflow_tpu.core import telemetry
from chunkflow_tpu.parallel.queues import QueueBase, open_queue
from chunkflow_tpu.parallel.restapi import scrape_worker

__all__ = [
    "WorkerHandle", "FleetSupervisor", "fleet_disabled",
    "host_available_gb", "host_tpu_chips", "COMPUTE_BOUND_PHASES",
    "STORAGE_BOUND_PHASES",
]

_OFF_VALUES = ("0", "off", "false", "no")

#: dominant-stall phases that mean "the fleet is limited by per-worker
#: compute/device throughput" — more workers genuinely add throughput
COMPUTE_BOUND_PHASES = (
    "pipeline/stage", "pipeline/dispatch", "pipeline/compute",
    "pipeline/drain", "scheduler/post",
)
#: phases that mean "the fleet is limited by shared storage" — adding
#: workers multiplies pressure on the same volume store for no gain
STORAGE_BOUND_PHASES = ("scheduler/load", "scheduler/write")


def fleet_disabled() -> bool:
    """``CHUNKFLOW_FLEET=0`` (or off/false/no): the kill switch. The
    supervisor still spawns and replaces workers — liveness is not
    optional — but holds a static size and never consults telemetry."""
    return os.environ.get(
        "CHUNKFLOW_FLEET", "1").strip().lower() in _OFF_VALUES


def host_available_gb() -> Optional[float]:
    """``MemAvailable`` from /proc/meminfo in GiB (None where the
    procfs field is missing — macOS, exotic containers — in which case
    the memory watermark simply does not gate)."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) / (1 << 20)
    except (OSError, ValueError, IndexError):
        pass
    return None


def _proc_rss_gb(pid: int) -> Optional[float]:
    """Resident set of one worker process in GiB (procfs; None off
    Linux). Used to estimate what one more worker would cost the
    host before the watermark check."""
    try:
        with open(f"/proc/{pid}/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE") / (1 << 30)
    except (OSError, ValueError, IndexError):
        return None


def _free_port(host: str) -> int:
    """An ephemeral port for a worker's metrics exporter — the FALLBACK
    for fleets running without a --metrics-dir. Bind-and-release is racy
    in principle; real spawns with a metrics dir instead pass
    ``--metrics-port 0`` and discover the actually-bound port from the
    worker's endpoint file (``restapi.write_endpoint_file``), which
    cannot race. A worker that loses the fallback race fails to bind,
    dies, and is replaced — the same recovery path as any other worker
    death."""
    with socket.socket() as s:
        s.bind((host if host != "0.0.0.0" else "", 0))
        return s.getsockname()[1]


class WorkerHandle:
    """One supervised worker process and everything the supervisor
    knows about it. ``state`` transitions::

        starting --first /healthz--> live
        live --SIGTERM (scale-down / spot drill)--> draining --> exited
        live/starting --probe misses--> quarantined (SIGKILL) --> exited
        any --process died--> exited
    """

    def __init__(self, ident: str, port: Optional[int], proc,
                 cmd: List[str]):
        self.ident = ident
        # None until discovered from the worker's endpoint file (the
        # --metrics-port 0 spawn path); probing waits for it
        self.port = port
        self.proc = proc
        self.cmd = cmd
        self.state = "starting"
        self.started = time.time()
        self.last_seen: Optional[float] = None
        self.misses = 0
        self.exit_code: Optional[int] = None
        self.exited_at: Optional[float] = None
        self.handles: List[str] = []
        self.handles_truncated = False
        self.inflight_leases = 0
        self.dominant_stall: Optional[dict] = None
        # last-scraped storage block-cache counters ({"hits", "misses"},
        # None until the worker reports any) — lets a storage-bound
        # hold tell cache-cold from genuinely load-bound
        self.storage_cache: Optional[dict] = None
        # last-scraped firing SLO objectives (restapi.firing_alerts);
        # the supervisor annotates its scale/hold events with these so
        # the ops timeline shows WHAT was out of spec when it decided
        self.slo_firing: List[str] = []
        self.drill = False
        self.drain_deadline: Optional[float] = None

    @property
    def running(self) -> bool:
        return self.exit_code is None and self.proc.poll() is None

    @property
    def active(self) -> bool:
        """Counts toward fleet capacity: running and not on its way
        out (a draining/quarantined worker's slot is already free for
        a replacement)."""
        return self.running and self.state in ("starting", "live")

    def to_record(self) -> dict:
        """The fleet-state JSON record ``fleet-status`` renders: a dead
        worker keeps its last-seen time and exit code — "unreachable"
        alone is useless at 3 a.m."""
        return {
            "worker": self.ident,
            "pid": getattr(self.proc, "pid", None),
            "port": self.port,
            "endpoint": (f"127.0.0.1:{self.port}"
                         if self.port is not None else None),
            "state": self.state,
            "started": self.started,
            "last_seen": self.last_seen,
            "exit_code": self.exit_code,
            "inflight_leases": self.inflight_leases,
        }


def host_tpu_chips() -> List[str]:
    """Device nodes of the TPU chips a process on this host could take:
    libtpu opens ``/dev/accel<n>`` up to v4 and ``/dev/vfio/<n>`` from
    v5 on. Read without jax — a supervisor that touched the backend
    would hold the chips its workers need."""
    return sorted(glob.glob("/dev/accel[0-9]*")
                  + glob.glob("/dev/vfio/[0-9]*"))


class FleetSupervisor:
    """Spawn, monitor, scale and evict a fleet of queue-fed workers.

    ``worker_args`` is the full chunkflow CLI argv of one worker
    *after* the group options — typically ``["fetch-task-from-queue",
    "-q", <queue>, ..., <pipeline stages>..., "delete-task-in-queue"]``
    — the supervisor prepends the interpreter and the per-worker
    ``--metrics-dir``/``--metrics-port`` group options itself.

    Injection points for tests: ``launcher(cmd, env) -> Popen-like``
    (spawn), ``scraper(endpoint, timeout) -> dict``
    (``restapi.scrape_worker``), ``mem_probe() -> GiB|None``
    (:func:`host_available_gb`).
    """

    def __init__(
        self,
        queue_spec: str,
        worker_args: List[str],
        *,
        min_workers: int = 1,
        max_workers: int = 4,
        interval: float = 2.0,
        scale_up_backlog: float = 4.0,
        idle_ticks: int = 2,
        probe_misses: int = 3,
        probe_timeout: float = 1.0,
        startup_grace: float = 30.0,
        term_grace: float = 10.0,
        mem_watermark_gb: float = 2.0,
        worker_mem_est_gb: float = 0.5,
        storage_hold_share: float = 0.5,
        cache_warm_share: float = 0.5,
        dead_letter_surge: int = 3,
        crash_limit: int = 3,
        crash_window: float = 60.0,
        crash_backoff: float = 10.0,
        drill_rate: float = 0.0,
        seed: Optional[int] = None,
        metrics_dir: Optional[str] = None,
        state_path: Optional[str] = None,
        host: str = "127.0.0.1",
        python: Optional[str] = None,
        worker_env: Optional[Dict[str, str]] = None,
        static: Optional[bool] = None,
        launcher: Optional[Callable] = None,
        scraper: Optional[Callable] = None,
        mem_probe: Optional[Callable] = None,
        visibility_timeout: float = 1800.0,
    ):
        if min_workers < 1 or max_workers < min_workers:
            raise ValueError(
                f"need 1 <= min_workers <= max_workers, got "
                f"{min_workers}..{max_workers}"
            )
        self.queue_spec = queue_spec
        self.queue: QueueBase = open_queue(
            queue_spec, visibility_timeout=visibility_timeout)
        self.worker_args = list(worker_args)
        self.min_workers = min_workers
        self.max_workers = max_workers
        self.interval = max(0.05, float(interval))
        self.scale_up_backlog = float(scale_up_backlog)
        self.idle_ticks = int(idle_ticks)
        self.probe_misses = int(probe_misses)
        self.probe_timeout = float(probe_timeout)
        self.startup_grace = float(startup_grace)
        self.term_grace = float(term_grace)
        self.mem_watermark_gb = float(mem_watermark_gb)
        self.worker_mem_est_gb = float(worker_mem_est_gb)
        self.storage_hold_share = float(storage_hold_share)
        self.cache_warm_share = float(cache_warm_share)
        self.dead_letter_surge = int(dead_letter_surge)
        self.crash_limit = int(crash_limit)
        self.crash_window = float(crash_window)
        self.crash_backoff = float(crash_backoff)
        self.drill_rate = float(drill_rate)
        self.rng = random.Random(seed)
        self.metrics_dir = metrics_dir
        self.state_path = state_path or (
            os.path.join(metrics_dir, "fleet-state.json")
            if metrics_dir else None
        )
        self.host = host
        self.python = python or sys.executable
        self.worker_env = dict(worker_env or {})
        platforms = self.worker_env.get(
            "JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", ""))
        chips = host_tpu_chips()
        if (max_workers > 1 and chips
                and (not platforms or "tpu" in platforms.split(","))):
            raise ValueError(
                f"fleet: this host has TPU chip(s) {chips} and workers "
                f"would run jax on them (JAX_PLATFORMS={platforms!r}). "
                f"A chip belongs to one process: the first worker takes "
                f"every chip and each further one dies at start-up with "
                f"'The TPU is already in use'. Run one worker "
                f"(--max-workers 1) and give it the host's chips with "
                f"inference --mesh data=N, or set JAX_PLATFORMS=cpu for "
                f"workers that need no chip"
            )
        self.static = fleet_disabled() if static is None else bool(static)
        self.launcher = launcher or self._spawn_process
        self.scraper = scraper or scrape_worker
        self.mem_probe = mem_probe or host_available_gb
        # probing needs the workers' /metrics listeners, which the
        # telemetry kill switch suppresses (workers inherit our env):
        # with telemetry off, supervision degrades to process liveness
        self.probing = telemetry.enabled()

        self.workers: List[WorkerHandle] = []
        self.target = min_workers
        self._seq = 0
        self._idle_count = 0
        self._last_dead: Optional[int] = None
        self._recent_dead: List[tuple] = []  # (t, delta) dead-letter surges
        self._deaths: List[float] = []       # unexpected-death timestamps
        self._backoff_until = 0.0
        self._drill_requested = 0
        self._stop = threading.Event()
        if "delete-task-in-queue" not in self.worker_args:
            print(
                "fleet: worker_args has no delete-task-in-queue stage — "
                "workers will never ack, the queue will never drain",
                file=sys.stderr,
            )

    # -- spawning -------------------------------------------------------
    def _spawn_process(self, cmd: List[str], env: Dict[str, str]):
        log = subprocess.DEVNULL
        if self.metrics_dir:
            os.makedirs(self.metrics_dir, exist_ok=True)
            log = open(
                os.path.join(
                    self.metrics_dir,
                    f"worker-{env['CHUNKFLOW_WORKER_ID']}.log"),
                "ab",
            )
        try:
            return subprocess.Popen(
                cmd, env=env, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True,  # our SIGINT must not strafe them
            )
        finally:
            if log is not subprocess.DEVNULL:
                log.close()  # the child holds its own descriptor

    def spawn_worker(self) -> WorkerHandle:
        self._seq += 1
        ident = f"fleet-w{self._seq:03d}"
        # real spawns with a metrics dir bind ephemeral (--metrics-port
        # 0) and publish the bound port in their endpoint file — no
        # pre-pick race, no collisions between workers on one host.
        # Injected launchers (tests) and dir-less fleets keep the
        # legacy pre-picked port, which is the only address the
        # supervisor could know for them.
        discover = (self.metrics_dir is not None
                    and self.launcher == self._spawn_process)
        port = None if discover else _free_port(self.host)
        cmd = [self.python, "-m", "chunkflow_tpu.flow.cli"]
        if self.metrics_dir:
            cmd += ["--metrics-dir", self.metrics_dir]
        cmd += ["--metrics-port", "0" if discover else str(port)]
        cmd += self.worker_args
        env = dict(os.environ)
        env.update(self.worker_env)
        env["CHUNKFLOW_WORKER_ID"] = ident
        env.pop("CHUNKFLOW_METRICS_PORT", None)  # --metrics-port wins
        # the worker must import chunkflow_tpu from wherever WE did
        # (editable checkouts, test trees) regardless of its cwd
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        worker = WorkerHandle(ident, port, self.launcher(cmd, env), cmd)
        self.workers.append(worker)
        telemetry.inc("fleet/spawns")
        telemetry.event(
            "fleet", "fleet/spawn", fleet_worker=ident,
            worker_pid=getattr(worker.proc, "pid", None), port=port,
        )
        return worker

    # -- probing + eviction ---------------------------------------------
    def _discover_port(self, worker: WorkerHandle) -> Optional[int]:
        """Resolve an ephemeral-spawned worker's bound metrics port from
        the endpoint file it publishes once its exporter is up."""
        if worker.port is not None:
            return worker.port
        if not self.metrics_dir:
            return None
        from chunkflow_tpu.parallel.restapi import read_endpoint_file

        record = read_endpoint_file(self.metrics_dir, worker.ident)
        if record and record.get("metrics_port"):
            worker.port = int(record["metrics_port"])
        return worker.port

    def _probe(self, worker: WorkerHandle, now: float) -> None:
        if not worker.running or worker.state not in ("starting", "live"):
            return
        if not self.probing:
            worker.state = "live"  # liveness only: running == healthy
            worker.last_seen = now
            return
        if self._discover_port(worker) is None:
            # no bound port published yet: indistinguishable from "the
            # exporter is not up yet" — same startup grace, then the
            # same probation as a worker that never answers
            if now - worker.started < self.startup_grace:
                return
            worker.misses += 1
            telemetry.inc("fleet/probe_failures")
            if worker.misses >= self.probe_misses:
                self._evict(
                    worker, f"no endpoint published after "
                            f"{now - worker.started:.0f}s")
            return
        sample = self.scraper(
            f"{self.host}:{worker.port}", timeout=self.probe_timeout)
        if sample.get("error") is None:
            health = sample.get("healthz") or {}
            worker.state = "live"
            worker.last_seen = now
            worker.misses = 0
            worker.inflight_leases = int(health.get("inflight_leases", 0))
            worker.handles = list(health.get("inflight_handles") or [])
            worker.handles_truncated = bool(
                health.get("inflight_handles_truncated"))
            worker.dominant_stall = sample.get("dominant_stall")
            metrics = sample.get("metrics") or {}
            hits = metrics.get("chunkflow_storage_hits_total")
            misses = metrics.get("chunkflow_storage_misses_total")
            worker.storage_cache = (
                {"hits": float(hits or 0), "misses": float(misses or 0)}
                if (hits is not None or misses is not None) else None
            )
            worker.slo_firing = list(sample.get("slo_firing") or [])
            return
        if worker.state == "starting" and \
                now - worker.started < self.startup_grace:
            return  # the exporter may simply not be up yet
        worker.misses += 1
        telemetry.inc("fleet/probe_failures")
        if worker.misses >= self.probe_misses:
            self._evict(worker, f"missed {worker.misses} health probes")

    def _evict(self, worker: WorkerHandle, reason: str) -> None:
        """Health probation expired: the worker is sick (wedged runtime,
        dead exporter, livelock) — quarantine it. SIGKILL, because a
        process that stopped answering /healthz cannot be trusted to
        honor SIGTERM either; its last-reported leases are force-nacked
        at reap so the fleet picks the work up immediately."""
        worker.state = "quarantined"
        telemetry.inc("fleet/evictions")
        telemetry.event(
            "fleet", "fleet/evict", fleet_worker=worker.ident,
            reason=reason, leases=len(worker.handles),
        )
        try:
            worker.proc.kill()
        except OSError:
            pass

    # -- graceful drain + spot drill ------------------------------------
    def _drain(self, worker: WorkerHandle, now: float,
               drill: bool = False) -> None:
        worker.state = "draining"
        worker.drill = drill
        worker.drain_deadline = now + self.term_grace
        try:
            worker.proc.send_signal(signal.SIGTERM)
        except OSError:
            pass  # already gone; reap will notice

    def request_drill(self) -> None:
        """Force one spot-drill preemption on the next tick (tests,
        `fleet-run --drill-now`) regardless of ``drill_rate``."""
        self._drill_requested += 1

    def _maybe_drill(self, now: float) -> None:
        due = self._drill_requested > 0 or (
            self.drill_rate > 0 and self.rng.random() < self.drill_rate
        )
        if not due:
            return
        victims = [w for w in self.workers if w.running and w.state == "live"]
        if not victims:
            return
        if self._drill_requested:
            self._drill_requested -= 1
        victim = self.rng.choice(victims)
        telemetry.inc("fleet/drill_preemptions")
        telemetry.event(
            "fleet", "fleet/drill", fleet_worker=victim.ident,
        )
        # the spot contract: a termination notice (SIGTERM), a short
        # deadline, then the hypervisor yanks the plug (reap + SIGKILL
        # via the drain deadline)
        self._drain(victim, now, drill=True)

    def _enforce_drain_deadlines(self, now: float) -> None:
        for worker in self.workers:
            if (worker.state == "draining" and worker.running
                    and worker.drain_deadline is not None
                    and now > worker.drain_deadline):
                try:
                    worker.proc.kill()
                except OSError:
                    pass

    # -- reaping --------------------------------------------------------
    def _reap(self, now: float) -> None:
        for worker in self.workers:
            if worker.exit_code is not None:
                continue
            code = worker.proc.poll()
            if code is None:
                continue
            worker.exit_code = code
            worker.exited_at = now
            # exit 0 is a worker that drained the queue and finished on
            # its own — a completion, not a death
            expected = code == 0 or worker.state in (
                "draining", "quarantined")
            worker.state = "exited"
            # whatever it still held goes back NOW — for an evicted or
            # crashed worker this is the difference between immediate
            # pickup and waiting out the visibility timeout; for a clean
            # drain the worker nacked (with refund) on SIGTERM itself,
            # so these releases are no-ops and count zero. The receive
            # count is NOT refunded here (force_release refund=False):
            # a crash/quarantine delivery must keep counting, or the
            # lifecycle crash-loop bound could never dead-letter a
            # poison task that kills every worker it lands on.
            released = self.queue.force_release(worker.handles)
            if released:
                telemetry.inc("fleet/leases_nacked", released)
            if worker.handles_truncated:
                # /healthz capped the handle list: the leases past the
                # cap were NOT force-nacked and will ride out the full
                # visibility timeout — surface it instead of silently
                # breaking the immediate-pickup guarantee
                telemetry.inc("fleet/handles_truncated")
                telemetry.event(
                    "fleet", "fleet/handles_truncated",
                    fleet_worker=worker.ident, released=released,
                    inflight_leases=worker.inflight_leases,
                )
            worker.handles = []
            worker.handles_truncated = False
            worker.inflight_leases = 0
            telemetry.event(
                "fleet", "fleet/exit", fleet_worker=worker.ident,
                exit_code=code, uptime_s=round(now - worker.started, 3),
                expected=expected,
            )
            if not expected:
                telemetry.inc("fleet/worker_deaths")
                self._deaths.append(now)
        # crash-loop probation: unexpected deaths arriving faster than
        # crash_limit per crash_window back respawning off — a poisoned
        # image or broken volume mount must not spin the host
        self._deaths = [t for t in self._deaths
                        if now - t <= self.crash_window]
        if len(self._deaths) >= self.crash_limit \
                and now >= self._backoff_until:
            self._backoff_until = now + self.crash_backoff
            telemetry.inc("fleet/crash_backoffs")
            telemetry.event(
                "fleet", "fleet/crash_backoff",
                deaths=len(self._deaths), backoff_s=self.crash_backoff,
            )

    # -- the controller -------------------------------------------------
    def _fleet_dominant(self) -> Optional[dict]:
        """Share-weighted dominant stall phase across the last probes
        (None until any worker reports one)."""
        totals: Dict[str, float] = {}
        for worker in self.workers:
            if worker.active and worker.dominant_stall:
                phase = worker.dominant_stall.get("phase")
                share = float(worker.dominant_stall.get("share", 0.0))
                if phase:
                    totals[phase] = totals.get(phase, 0.0) + share
        if not totals:
            return None
        phase = max(totals, key=totals.get)
        n = sum(1 for w in self.workers
                if w.active and w.dominant_stall)
        return {"phase": phase, "share": totals[phase] / n}

    def _storage_hit_rate(self) -> Optional[float]:
        """Fleet-wide storage block-cache hit rate from the last worker
        scrapes; None when no active worker reports storage counters
        (pre-storage-plane workers, telemetry off)."""
        hits = misses = 0.0
        seen = False
        for worker in self.workers:
            if worker.active and worker.storage_cache is not None:
                seen = True
                hits += worker.storage_cache.get("hits", 0.0)
                misses += worker.storage_cache.get("misses", 0.0)
        if not seen or hits + misses <= 0:
            return None
        return hits / (hits + misses)

    def _mem_ok(self) -> bool:
        available = self.mem_probe()
        if available is None:
            return True  # no procfs: the watermark cannot gate
        telemetry.gauge("fleet/host_available_gb", round(available, 3))
        est = self.worker_mem_est_gb
        rss = [r for r in (_proc_rss_gb(getattr(w.proc, "pid", -1))
                           for w in self.workers if w.active)
               if r is not None]
        if rss:
            est = max(est, sum(rss) / len(rss))
        return available - est >= self.mem_watermark_gb

    def _dead_letter_surging(self, stats: dict, now: float) -> bool:
        dead = stats.get("dead")
        if dead is None:
            return False
        if self._last_dead is not None and dead > self._last_dead:
            self._recent_dead.append((now, dead - self._last_dead))
        self._last_dead = dead
        window = self.interval * 5
        self._recent_dead = [(t, d) for t, d in self._recent_dead
                             if now - t <= window]
        return sum(d for _, d in self._recent_dead) >= self.dead_letter_surge

    def _fleet_slo_firing(self) -> List[str]:
        """Union of the firing SLO objectives across the last active
        worker scrapes (restapi.firing_alerts) — the annotation every
        scale/hold decision carries. Annotation ONLY in this PR: the
        controller does not yet act on it (the policy half of the SLO
        closed loop is a later PR), but the ops timeline already shows
        what was out of spec at each decision."""
        firing: set = set()
        for worker in self.workers:
            if worker.active:
                firing.update(worker.slo_firing)
        return sorted(firing)

    def _slo_attrs(self) -> dict:
        firing = self._fleet_slo_firing()
        return {"slo_firing": firing} if firing else {}

    def _hold(self, reason: str) -> None:
        telemetry.inc("fleet/holds")
        telemetry.event("fleet", "fleet/hold", reason=reason,
                        **self._slo_attrs())

    def _decide(self, stats: dict, now: float) -> None:
        """One controller tick: move ``self.target`` by at most one,
        from live signals. Static mode bypasses all of it."""
        if self.static:
            self.target = self.min_workers
            return
        active = sum(1 for w in self.workers if w.active)
        pending = stats.get("pending")
        inflight = stats.get("inflight")
        dead_surge = self._dead_letter_surging(stats, now)

        # scale DOWN: a queue idle for idle_ticks straight means the
        # volume is drained (or starved upstream) — fall back to min
        if pending == 0 and inflight == 0:
            self._idle_count += 1
        else:
            self._idle_count = 0
        if self._idle_count >= self.idle_ticks \
                and self.target > self.min_workers:
            telemetry.inc("fleet/scale_down")
            telemetry.event(
                "fleet", "fleet/scale", direction="down",
                target=self.min_workers, reason="idle-queue",
                **self._slo_attrs(),
            )
            self.target = self.min_workers
            return

        # scale UP: deep queue, one worker per tick, gated on
        # compute-boundness, memory headroom and dead-letter sanity
        if pending is None or self.target >= self.max_workers:
            return
        if pending <= self.scale_up_backlog * max(1, active):
            return
        if dead_surge:
            self._hold("dead-letter-surge")
            return
        dominant = self._fleet_dominant()
        if dominant and dominant["phase"] in STORAGE_BOUND_PHASES \
                and dominant["share"] >= self.storage_hold_share:
            # qualify the hold with the block-cache hit rate when the
            # workers report one (volume/storage.py): a cold cache means
            # the stall is transient re-fetch traffic the warming LRU
            # will absorb; a warm cache still storage-bound means the
            # shared store genuinely is the limit — different 3 a.m.
            # responses (wait vs. shard the volume / add bandwidth)
            reason = f"storage-bound:{dominant['phase']}"
            hit_rate = self._storage_hit_rate()
            if hit_rate is not None:
                reason += (":cold-cache"
                           if hit_rate < self.cache_warm_share
                           else ":load-bound")
            self._hold(reason)
            return
        if not self._mem_ok():
            self._hold("memory-watermark")
            return
        if now < self._backoff_until:
            self._hold("crash-backoff")
            return
        self.target += 1
        telemetry.inc("fleet/scale_up")
        telemetry.event(
            "fleet", "fleet/scale", direction="up", target=self.target,
            reason="deep-queue", pending=pending,
            dominant=(dominant or {}).get("phase"),
            **self._slo_attrs(),
        )

    def _enact(self, now: float) -> None:
        active = [w for w in self.workers if w.active]
        if len(active) > self.target:
            # drain newest-first: the eldest workers have warm compile
            # caches and deserve to keep them
            for worker in sorted(active, key=lambda w: w.started,
                                 reverse=True)[: len(active) - self.target]:
                telemetry.inc("fleet/scale_down_drains")
                self._drain(worker, now)
        elif len(active) < self.target and now >= self._backoff_until:
            for _ in range(self.target - len(active)):
                self.spawn_worker()

    # -- state + the loop -----------------------------------------------
    def write_state(self) -> Optional[str]:
        """Atomic fleet-state JSON for ``fleet-status``: every worker
        this supervisor ever owned, with last-seen and exit codes."""
        if self.state_path is None:
            return None
        payload = {
            "t": time.time(),
            "queue": self.queue_spec,
            "static": self.static,
            "target": self.target,
            "min_workers": self.min_workers,
            "max_workers": self.max_workers,
            "supervisor_pid": os.getpid(),
            "workers": [w.to_record() for w in self.workers],
        }
        os.makedirs(os.path.dirname(self.state_path) or ".", exist_ok=True)
        tmp = f"{self.state_path}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
        os.replace(tmp, self.state_path)
        return self.state_path

    def step(self) -> dict:
        """One decision interval: reap, probe, drill, decide, enact,
        publish. Returns the queue stats the decision used."""
        now = time.time()
        self._reap(now)
        self._enforce_drain_deadlines(now)
        for worker in self.workers:
            self._probe(worker, now)
        self._maybe_drill(now)
        try:
            stats = self.queue.stats()
        except Exception:  # a flaky queue tick must not kill the fleet
            stats = {"pending": None, "inflight": None, "dead": None,
                     "receives": None}
        self._decide(stats, now)
        self._enact(now)
        active = sum(1 for w in self.workers if w.active)
        telemetry.gauge("fleet/workers", active)
        telemetry.gauge("fleet/target", self.target)
        if stats.get("pending") is not None:
            telemetry.gauge("fleet/pending", stats["pending"])
        if stats.get("inflight") is not None:
            telemetry.gauge("fleet/inflight", stats["inflight"])
        self.write_state()
        return stats

    def _drained(self, stats: dict) -> bool:
        pending = stats.get("pending")
        inflight = stats.get("inflight")
        if inflight is None:  # backend can't say: use the probed leases
            if not self.probing:
                # telemetry off AND a blind backend: claimed-but-unacked
                # tasks are invisible to us entirely, so pending == 0 is
                # a guess — run() demands it persist for extra ticks
                # (_settle_target) instead of assuming zero leases
                return pending == 0
            # draining/quarantined workers keep their last probed lease
            # count until reaped, so sum over every running worker, not
            # just the active ones
            inflight = sum(w.inflight_leases for w in self.workers
                           if w.running)
        return pending == 0 and inflight == 0

    def _settle_target(self, stats: dict, settle_ticks: int) -> int:
        """Consecutive drained ticks required before declaring the
        queue done. When the backend cannot report inflight and probing
        is off, in-flight leases are invisible — pending hits 0 the
        moment the LAST tasks are claimed, not when they finish — so
        demand a much longer quiet period before SIGTERMing workers
        that may still be mid-compute."""
        if stats.get("inflight") is not None or self.probing:
            return settle_ticks
        return max(3 * settle_ticks, settle_ticks + 3)

    def run(self, max_runtime: float = 3600.0, settle_ticks: int = 2,
            shutdown_on_drain: bool = True) -> dict:
        """Supervise until the queue drains (``pending == inflight ==
        0`` for ``settle_ticks`` consecutive ticks), ``stop()`` is
        called, or ``max_runtime`` elapses. With
        ``shutdown_on_drain=False`` the fleet is left running at target
        size for the caller to inspect (the acceptance test asserts the
        survivor count) — call :meth:`shutdown` afterwards."""
        deadline = time.time() + max_runtime
        settled = 0
        telemetry.event(
            "fleet", "fleet/start", queue=self.queue_spec,
            static=self.static, min=self.min_workers, max=self.max_workers,
        )
        try:
            while not self._stop.is_set() and time.time() < deadline:
                stats = self.step()
                settled = settled + 1 if self._drained(stats) else 0
                if settled >= self._settle_target(stats, settle_ticks):
                    break
                self._stop.wait(self.interval)
        except BaseException:
            self.shutdown()  # never leave orphan workers behind
            raise
        if shutdown_on_drain:
            self.shutdown()
        else:
            self.write_state()
        return self.summary()

    def stop(self) -> None:
        self._stop.set()

    def shutdown(self) -> None:
        """Graceful fleet teardown: SIGTERM everyone (their preemption
        handlers nack + flush), hard-kill stragglers past
        ``term_grace``, reap, and write the final state file."""
        now = time.time()
        for worker in self.workers:
            if worker.running and worker.state != "draining":
                self._drain(worker, now)
        deadline = now + self.term_grace
        while time.time() < deadline and any(
                w.running for w in self.workers):
            time.sleep(0.05)
        for worker in self.workers:
            if worker.running:
                try:
                    worker.proc.kill()
                except OSError:
                    pass
        for worker in self.workers:
            if worker.exit_code is None:
                try:
                    worker.proc.wait(timeout=5.0)
                except Exception:
                    pass
        self._reap(time.time())
        self.write_state()
        telemetry.event("fleet", "fleet/stop")

    def summary(self) -> dict:
        counters = telemetry.snapshot()["counters"]
        return {
            "target": self.target,
            "alive": sum(1 for w in self.workers if w.active),
            "spawned": self._seq,
            "scale_ups": counters.get("fleet/scale_up", 0),
            "scale_downs": counters.get("fleet/scale_down", 0),
            "evictions": counters.get("fleet/evictions", 0),
            "worker_deaths": counters.get("fleet/worker_deaths", 0),
            "drill_preemptions": counters.get("fleet/drill_preemptions", 0),
            "leases_nacked": counters.get("fleet/leases_nacked", 0),
            "holds": counters.get("fleet/holds", 0),
            "static": self.static,
        }
