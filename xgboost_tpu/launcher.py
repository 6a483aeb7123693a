"""Multi-process training launcher — the role of the reference's dask/spark
launchers (python-package/xgboost/dask/__init__.py:722 _train_async: one
worker per data shard, rabit rendezvous, identical models out).

There is no dask in the TPU stack: jax.distributed is the rendezvous and the
collective, so the launcher's job reduces to spawning one process per worker
with the coordinator address wired through ``collective.init``.  Each worker
runs ``fn(rank, world_size)``; inside, build a DMatrix on the worker's shard
and call ``xgboost_tpu.train`` — cuts merge through the distributed sketch
and histograms allreduce per level, so every worker returns the same model
(tested in tests/test_multiprocess.py).

Example worker::

    def worker(rank, world):
        import xgboost_tpu as xtb
        X, y = load_shard(rank, world)
        bst = xtb.train(params, xtb.DMatrix(X, label=y), 100)
        if rank == 0:
            bst.save_model("model.ubj")

    from xgboost_tpu.launcher import run_distributed
    run_distributed(worker, num_workers=4)
"""
from __future__ import annotations

import functools
import os
import pickle
import socket
import subprocess
import sys
import tempfile
from typing import Callable, Optional


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class WorkerFailedError(RuntimeError):
    """One or more spawned workers exited non-zero.

    ``failures`` holds ``(label, returncode, stderr_tail)`` per failed
    worker — ``label`` is the spawn index (the tracker may have assigned a
    different collective rank; the worker's own stderr says which), and
    ``stderr_tail`` is the captured tail of that process's stderr, so the
    first-failure cause survives instead of every peer's death reading as
    a generic rendezvous hang."""

    def __init__(self, message: str, failures) -> None:
        super().__init__(message)
        self.failures = list(failures)


def stderr_tail(path: str, limit: int = 4000) -> str:
    """Last ``limit`` bytes of a spawned worker's captured stderr file
    (what :class:`WorkerFailedError.failures` carries per corpse)."""
    try:
        with open(path, "rb") as fh:
            fh.seek(0, os.SEEK_END)
            size = fh.tell()
            fh.seek(max(size - limit, 0))
            return fh.read().decode("utf-8", "replace").strip()
    except OSError:
        return "<stderr unavailable>"


def spawn_worker(argv, label, err_files: dict, *, env=None):
    """Spawn one worker subprocess with per-process stderr capture.

    The launcher's stderr-to-file discipline as a reusable primitive (the
    serving fleet spawns replicas through it): stderr goes to a temp file
    recorded in ``err_files[label]`` — not a pipe, since nobody drains
    pipes while workers run and the tail must survive the process — so a
    death surfaces its actual cause via :func:`stderr_tail`, not a bare
    exit code.  The child also inherits a flight-recorder identity
    (``XGBOOST_TPU_FLIGHT_DIR``/``_LABEL``), so its crash/spill dump
    lands at :func:`flight_dump_path` for this label.  Returns the
    ``subprocess.Popen``; the caller owns reaping and unlinking
    ``err_files`` values."""
    from .telemetry import flight

    fd, err_path = tempfile.mkstemp(prefix=f"xtb_worker_{label}_",
                                    suffix=".stderr")
    err_files[label] = err_path
    env = dict(env if env is not None else os.environ)
    env.setdefault(flight.ENV_DIR, flight.dump_dir())
    env[flight.ENV_LABEL] = str(label)
    with os.fdopen(fd, "wb") as ef:
        return subprocess.Popen(argv, env=env, stderr=ef)


def flight_dump_path(label) -> Optional[str]:
    """The flight-recorder dump a worker spawned with ``label`` would
    have left (crash dump, periodic spill, or atexit) — None when the
    process never wrote one (e.g. SIGKILL before the first spill)."""
    from .telemetry import flight

    path = flight.default_path(str(label))
    return path if os.path.exists(path) else None


def stack_dump_path(label) -> Optional[str]:
    """The all-thread ``faulthandler`` dump a worker spawned with
    ``label`` would have left (crash path, injected kill, watchdog dump
    stage) — None when none was written."""
    from .telemetry import flight

    path = flight.stacks_path(str(label))
    return path if os.path.exists(path) else None


def _postmortem_tail(label, tail: str) -> str:
    """Append the flight-recorder and stack-dump pointers a corpse left
    to its stderr tail (what WorkerFailedError.failures carries)."""
    fp = flight_dump_path(label)
    if fp:
        tail += f"\n[flight recorder: {fp}]"
    sp = stack_dump_path(label)
    if sp:
        tail += f"\n[stack dump: {sp}]"
    return tail


_TRACKER_CHILD = r"""
import sys

host, port, world = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
elastic, journal = sys.argv[4] == "1", sys.argv[5]
if sys.argv[6]:
    sys.path.insert(0, sys.argv[6])  # the xgboost_tpu package root

from xgboost_tpu.telemetry import flight, profiler
from xgboost_tpu.tracker import RabitTracker

flight.install()  # label "tracker"/"tracker_r<N>" from the launcher env
profiler.maybe_start("tracker")  # relay loops join the merged flame view
tr = RabitTracker(n_workers=world, host_ip=host, port=port,
                  elastic=elastic, journal=journal)
tr.start()
try:
    # block until the job finishes; the LAUNCHER owns the overall
    # deadline and kills this process when the run is over or failed
    tr.wait_for(timeout=0)
except RuntimeError:
    # the job failed — the abort already fanned out to every worker.
    # Exit 1 tells the launcher "job error", distinct from a crash
    # (any other status), which is what triggers a respawn.
    sys.exit(1)
finally:
    tr.free()
"""


def _tracker_connectable(port: int, deadline_s: float = 30.0) -> bool:
    """Poll until the tracker child accepts connections (its import +
    bind window).  The probe connection EOFs without a handshake, which
    the tracker's accept loops already treat as a stray scan."""
    import time

    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        try:
            socket.create_connection(("127.0.0.1", int(port)),
                                     timeout=1.0).close()
            return True
        except OSError:
            time.sleep(0.1)
    return False


_CHILD = r"""
import pickle, sys
import jax

platform = sys.argv[4]
if platform:
    jax.config.update("jax_platforms", platform)
if sys.argv[6]:
    sys.path.insert(0, sys.argv[6])  # make fn's defining module importable
from xgboost_tpu import collective
from xgboost_tpu.telemetry import flight, profiler, trace

flight.install()  # ring spill + crash dump under the launcher's label env
profiler.maybe_start()  # default-on sampler; label set by training.train

rank = sys.argv[1]  # spawn label; an int only in direct mode ("respawn<N>"
                    # labels exist in elastic tracker mode)
world = int(sys.argv[2])
port = sys.argv[3]
if sys.argv[7] == "tracker":
    # tracker rendezvous: rank assigned by the tracker, persistent abort
    # channel, socket-relay collectives on CPU backends (tracker.CollRelay)
    collective.init(dmlc_tracker_uri="127.0.0.1", dmlc_tracker_port=port,
                    dmlc_nworker=world)
    rank = collective.get_rank()
    # elastic replacements join at the CURRENT world size, not the
    # originally requested one
    world = collective.get_world_size()
else:
    rank = int(rank)
    collective.init(coordinator_address=f"127.0.0.1:{port}",
                    num_processes=world, process_id=rank)
if trace.active():
    trace.set_process_name(f"rank{rank}")
with open(sys.argv[5], "rb") as fh:
    fn = pickle.load(fh)
try:
    fn(rank, world)
except BaseException as e:
    # postmortem without tracing: the ring of recent spans/events/faults
    # survives as a dump the launcher attaches to WorkerFailedError —
    # plus an all-thread faulthandler dump (what were the OTHER threads
    # doing: prefetch pools, relay watchers, telemetry shippers)
    flight.record("fault", "worker.crash", error=repr(e))
    flight.dump_stacks()
    flight.dump()
    raise
finally:
    collective.finalize()
"""


def run_distributed(fn: Callable[[int, int], None], num_workers: int,
                    *, coordinator_port: Optional[int] = None,
                    platform: Optional[str] = None,
                    timeout: float = 3600.0,
                    fault_plan: Optional[str] = None,
                    rendezvous: str = "auto",
                    elastic: bool = False,
                    max_respawns: int = 0,
                    tracker_failover: bool = False,
                    max_tracker_respawns: int = 3) -> dict:
    """Spawn ``num_workers`` processes, each running ``fn(rank, world)``
    under an initialized collective.  ``fn`` must be picklable (a module-
    level function).  ``platform`` overrides jax_platforms in the workers
    (e.g. "cpu" for tests).  Raises on the first failing worker.

    ``fault_plan``: inline JSON or a file path, exported to the workers as
    ``XGBOOST_TPU_FAULT_PLAN`` (reliability/faults.py) — the hook the
    fault-injection tests and the nightly kill/resume smoke use.

    ``rendezvous``: "direct" (jax.distributed coordinator, pre-assigned
    ranks) or "tracker" (a RabitTracker assigns ranks, keeps the abort
    fan-out channel, and supplies socket-relay collectives on CPU backends
    — required for CPU multi-process training, docs/reliability.md).
    "auto" picks "tracker" for CPU workers (XLA:CPU cannot run
    multiprocess collectives, and the abort fan-out is strictly more
    robust locally) and "direct" for accelerator platforms.

    ``elastic``: the tracker runs in elastic mode — a worker dying no
    longer fails the job; the survivors regroup at world N-1 and keep
    training (workers must pass ``train(..., elastic=...)`` for the data
    re-sharding side).  Requires tracker rendezvous.  ``max_respawns``
    bounds how many replacement workers the launcher spawns after deaths;
    each connects to the tracker and is absorbed at the next round
    boundary.  Exit code 255 (tracker abort fan-out: an explicitly
    signalled error) still fails the job even in elastic mode.

    ``tracker_failover``: the tracker runs as a SUPERVISED SUBPROCESS
    journaling its replayable state (roster, epoch, per-rank resume
    rounds — reliability/journal.py); a crashed/SIGKILL'd tracker is
    respawned (up to ``max_tracker_respawns`` times) and recovers from
    the journal, the surviving workers re-adopt with backoff, and the
    run continues through an elastic regroup at the same world size —
    bitwise-identical model bytes under deterministic config (the
    coordinator stops being a single point of failure;
    docs/reliability.md "Coordinator failover & watchdog").  Requires
    ``elastic=True``.  A respawned tracker starts with a CLEAN fault-plan
    environment, so a plan that killed the first tracker cannot re-kill
    every successor.  Note the merged-telemetry ingest then happens in
    the tracker subprocess, not this driver.

    Failures raise :class:`WorkerFailedError` carrying each failed
    worker's spawn index, exit code, and captured stderr tail.  Returns a
    stats dict: tolerated worker deaths, worker respawns, tracker
    respawns, and each tracker-respawn pause wall (death detection to
    the respawned tracker accepting again) in seconds."""
    tracker = None
    tracker_proc = None
    journal_dir = None
    # opt-in driver-side scrape endpoint (XGBOOST_TPU_METRICS_PORT): the
    # tracker ingests worker snapshot ships into the merged registry, and
    # /metrics serves per-rank plus merged series while the job runs
    from .telemetry.distributed import start_metrics_server

    start_metrics_server()
    if rendezvous == "auto":
        rendezvous = "tracker" if (platform or "") == "cpu" else "direct"
    if elastic and rendezvous != "tracker":
        raise ValueError("elastic mode requires rendezvous='tracker' "
                         "(relay collectives re-form at regroup; a "
                         "jax.distributed world cannot rescale)")
    if tracker_failover and (rendezvous != "tracker" or not elastic):
        raise ValueError("tracker_failover requires rendezvous='tracker' "
                         "AND elastic=True: a re-adopted cohort recovers "
                         "through the elastic regroup + checkpoint path")
    if rendezvous == "tracker" and not tracker_failover:
        from .tracker import RabitTracker

        tracker = RabitTracker(n_workers=num_workers, host_ip="127.0.0.1",
                               elastic=elastic)
        tracker.start()
        port = tracker.port
    elif rendezvous == "tracker":
        port = _free_port()  # the tracker child binds it (and rebinds it
        #                      on every respawn — workers only know this
        #                      address)
        journal_dir = tempfile.mkdtemp(prefix="xtb_tracker_journal_")
    elif rendezvous == "direct":
        port = coordinator_port or _free_port()
    else:
        raise ValueError(f"unknown rendezvous {rendezvous!r}")
    with tempfile.NamedTemporaryFile(suffix=".pkl", delete=False) as fh:
        pickle.dump(fn, fh)
        fn_path = fh.name
    target = fn
    while isinstance(target, functools.partial):
        target = target.func  # resolve the real function's home module
    mod = sys.modules.get(getattr(target, "__module__", ""), None)
    mod_dir = (os.path.dirname(os.path.abspath(mod.__file__))
               if mod is not None and getattr(mod, "__file__", None) else "")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    if fault_plan is not None:
        env["XGBOOST_TPU_FAULT_PLAN"] = fault_plan
    import time

    err_files = {}

    def _spawn(label):
        return spawn_worker(
            [sys.executable, "-c", _CHILD, str(label),
             str(num_workers), str(port), platform or "", fn_path,
             mod_dir, rendezvous],
            label, err_files, env=env)

    tracker_respawns = 0
    tracker_pauses = []  # seconds, death detection -> accepting again

    def _spawn_tracker(label):
        t_env = dict(env)
        if tracker_respawns:
            # a respawned coordinator must start with a clean plan: the
            # per-process seam counters restart at 0, so the spec that
            # killed the first tracker would re-fire in every successor
            t_env.pop("XGBOOST_TPU_FAULT_PLAN", None)
        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        argv = [sys.executable, "-c", _TRACKER_CHILD, "127.0.0.1",
                str(port), str(num_workers), "1" if elastic else "0",
                os.path.join(journal_dir, "tracker.xtbjrnl"), pkg_root]
        return spawn_worker(argv, label, err_files, env=t_env)

    if tracker_failover:
        tracker_proc = _spawn_tracker("tracker")
        if not _tracker_connectable(port):
            tracker_proc.kill()
            raise WorkerFailedError(
                "tracker subprocess never became connectable; stderr "
                "tail:\n" + stderr_tail(err_files["tracker"]),
                [("tracker", tracker_proc.poll(),
                  stderr_tail(err_files["tracker"]))])

    pending = {rank: _spawn(rank) for rank in range(num_workers)}
    respawned = 0
    succeeded = 0
    tolerated = []  # (label, rc) deaths survived in elastic mode
    try:
        deadline = time.monotonic() + timeout
        failures = []  # (label, rc, stderr_tail)
        while pending:
            if tracker_proc is not None:
                rc_t = tracker_proc.poll()
                if rc_t is not None:
                    if rc_t == 1:
                        # the tracker declared the JOB failed (it already
                        # fanned the abort out): stop supervising; the
                        # workers' 255 exits carry the failure below
                        tracker_proc = None
                    elif rc_t == 0:
                        # clean completion: the workers are finishing too
                        tracker_proc = None
                    elif tracker_respawns >= max_tracker_respawns:
                        for p in pending.values():
                            p.kill()
                        raise WorkerFailedError(
                            f"tracker crashed (exit {rc_t}) with the "
                            f"respawn budget ({max_tracker_respawns}) "
                            "spent", [("tracker", rc_t,
                                       stderr_tail(err_files.get(
                                           f"tracker_r{tracker_respawns}"
                                           if tracker_respawns
                                           else "tracker", "")))])
                    else:
                        # coordinator crash (SIGKILL, injected kill, bug):
                        # respawn it against the journal — the workers
                        # are re-adopting with backoff meanwhile, and the
                        # pause ends when the new tracker accepts
                        t0 = time.monotonic()
                        tracker_respawns += 1
                        print(f"[launcher] tracker exited {rc_t}; "
                              f"respawning against the journal "
                              f"({tracker_respawns}/{max_tracker_respawns})",
                              flush=True)
                        tracker_proc = _spawn_tracker(
                            f"tracker_r{tracker_respawns}")
                        if not _tracker_connectable(port):
                            for p in pending.values():
                                p.kill()
                            raise WorkerFailedError(
                                "respawned tracker never became "
                                "connectable",
                                [("tracker", rc_t, stderr_tail(
                                    err_files[
                                        f"tracker_r{tracker_respawns}"]))])
                        tracker_pauses.append(time.monotonic() - t0)
            for label, p in list(pending.items()):
                rc = p.poll()
                if rc is None:
                    continue
                del pending[label]
                if rc == 0:
                    succeeded += 1
                    continue
                tail = stderr_tail(err_files[label])
                # a death after peers already finished is still a
                # survivable death (a watchdog-declared stall wakes and
                # dies LAST, after the survivors completed the run) —
                # only "nobody succeeded and nobody is left" is fatal
                survivors_exist = succeeded > 0
                # a death during the initial rendezvous cannot be
                # regrouped (the tracker is still collecting the cohort);
                # tolerating it would leave the survivors blocked in
                # their handshakes until the full job timeout.  With a
                # subprocess tracker the journal's existence IS the
                # rendezvous-complete signal: its first record is the
                # initial roster.
                regroupable = (
                    (tracker is not None and tracker.rendezvous_complete)
                    or (journal_dir is not None and os.path.exists(
                        os.path.join(journal_dir, "tracker.xtbjrnl"))))
                if (elastic and rc != 255 and regroupable
                        and (pending or survivors_exist)):
                    # a death the survivors absorb (rc 255 means the
                    # tracker itself declared the job failed)
                    tolerated.append((label, rc))
                    print(f"[launcher] elastic: worker {label} exited "
                          f"{rc}; {len(pending)} continuing"
                          + (f"\n--- worker {label} stderr tail ---\n{tail}"
                             if tail else ""), flush=True)
                    if respawned < max_respawns:
                        respawned += 1
                        new_label = f"respawn{respawned}"
                        pending[new_label] = _spawn(new_label)
                    continue
                failures.append((label, rc, tail))
            if failures:
                # fail fast: peers would otherwise block in rendezvous or a
                # collective forever, waiting for the dead worker
                for p in pending.values():
                    p.kill()
                # attach each corpse's flight-recorder dump (crash dump or
                # last periodic spill) and its all-thread faulthandler
                # stack dump — the pair that makes the postmortem possible
                # without tracing or a debugger
                failures = [(r, rc, _postmortem_tail(r, tail))
                            for r, rc, tail in failures]
                labels = [f[0] for f in failures]
                detail = ", ".join(
                    f"rank {r}: " + ("aborted by tracker fan-out"
                                     if rc == 255 else f"exit {rc}")
                    for r, rc, _t in failures)
                msg = (f"worker(s) {labels} exited non-zero ({detail}); "
                       f"remaining workers killed")
                for r, _rc, tail in failures:
                    if tail:
                        msg += (f"\n--- worker {r} stderr tail ---\n{tail}")
                raise WorkerFailedError(msg, failures)
            if pending and time.monotonic() > deadline:
                for p in pending.values():
                    p.kill()
                raise TimeoutError(
                    f"worker(s) {sorted(pending, key=str)} still running "
                    f"after {timeout}s; killed")
            if pending:
                time.sleep(0.2)
    finally:
        if tracker is not None:
            tracker.free()
        if tracker_proc is not None:
            tracker_proc.kill()
        if journal_dir is not None:
            import shutil

            shutil.rmtree(journal_dir, ignore_errors=True)
        try:
            os.unlink(fn_path)
        except OSError:
            pass
        for path in err_files.values():
            try:
                os.unlink(path)
            except OSError:
                pass
    return {"tolerated": list(tolerated), "respawned": respawned,
            "succeeded": succeeded, "tracker_respawns": tracker_respawns,
            "tracker_pauses_s": tracker_pauses}
