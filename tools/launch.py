#!/usr/bin/env python
"""Multi-process launcher (reference: ``tools/launch.py`` + dmlc_tracker).

The reference spawned scheduler/server/worker processes and exported
``DMLC_*`` env vars for ps-lite. Here there are only *workers*: each process
is one jax.distributed participant; the coordinator is worker 0. Same UX::

    python tools/launch.py -n 4 python train.py --kv-store dist_sync

Local mode forks N processes on this host (the reference's ``--launcher
local`` CI topology, SURVEY §4 fixture #5); ssh mode prints per-host
commands (zero-egress environments can't ssh out, so it stops at the plan).

A TPU chip belongs to one process at a time, and workers started here see
whatever this process sees. So on a host with TPU chips ``-n`` > 1 is
refused, unless the workers are pinned off the TPU (``JAX_PLATFORMS=cpu``,
the CI shape) or the caller has taken charge of chip visibility
(``TPU_VISIBLE_CHIPS`` set, e.g. narrowed per ``MXNET_TPU_LOCAL_RANK`` by a
wrapper command). One host's four chips are driven by ONE process and a
four-device mesh (``parallel.Layout``), not by four workers; ``-n`` counts
hosts' worth of processes.

Elastic mode (``--elastic``, docs/RESILIENCE.md "Elastic training") wraps
local mode in a *supervising* loop: when a worker dies (crash, SIGKILL,
preemption) or exits with the re-formation code (75, EX_TEMPFAIL — see
``mxnet_tpu.resilience.elastic``), the supervisor tears the surviving
generation down, picks the next world size (1:1 replacement, or scale-down
under ``--elastic-policy shrink``), and respawns every rank against a fresh
coordinator address with an incremented generation — the job resumes from
its latest valid checkpoint without ever leaving this process tree. The
restart budget (``--max-restarts``) bounds how many re-formations a job may
spend before the supervisor gives up and propagates the failure.
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time

#: exit code a worker uses to request a mesh re-formation (kept in sync
#: with mxnet_tpu.resilience.elastic.ELASTIC_RESTART_EXIT without importing
#: the package — the launcher must run from a bare checkout/venv)
ELASTIC_RESTART_EXIT = 75


def shared_chip_refusal(n: int, env, chips) -> str | None:
    """Why ``n`` local workers may not start on a host whose TPU device
    nodes are ``chips``, or None when they may (see the module docstring).
    The launcher never imports jax — it would hold the chip itself."""
    if n <= 1 or not chips:
        return None
    platforms = [p for p in env.get("JAX_PLATFORMS", "").split(",") if p]
    if platforms and "tpu" not in platforms:
        return None
    if env.get("TPU_VISIBLE_CHIPS"):
        return None
    return (f"this host has TPU chips ({', '.join(chips)}) and each of the "
            f"{n} workers would claim all of them. Drive one host's chips "
            "from ONE process and a mesh (parallel.Layout); or pin the "
            "workers off the TPU (JAX_PLATFORMS=cpu); or set "
            "TPU_VISIBLE_CHIPS and narrow it per MXNET_TPU_LOCAL_RANK.")


def free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env(rank: int, n: int, coord: str, extra=None) -> dict:
    env = dict(os.environ)
    env.update({
        "MXNET_TPU_COORDINATOR": coord,
        "MXNET_TPU_NPROC": str(n),
        "MXNET_TPU_PROCID": str(rank),
        # all-local launch: local_rank == rank, local_size == n
        "MXNET_TPU_LOCAL_RANK": str(rank),
        "MXNET_TPU_LOCAL_SIZE": str(n),
        # reference-compat aliases so DMLC-era scripts keep working
        "DMLC_ROLE": "worker",
        "DMLC_NUM_WORKER": str(n),
        "DMLC_WORKER_ID": str(rank),
    })
    if extra:
        env.update(extra)
    return env


def _terminate(procs, grace: float = 5.0) -> None:
    """Stop every still-running worker: SIGTERM, a grace window (their
    preemption guards may want to flush), then SIGKILL the stragglers."""
    alive = [p for p in procs if p.poll() is None]
    for p in alive:
        try:
            p.terminate()
        except OSError:
            pass
    deadline = time.time() + grace
    for p in alive:
        try:
            p.wait(timeout=max(0.0, deadline - time.time()))
        except subprocess.TimeoutExpired:
            try:
                p.kill()
                p.wait()
            except OSError:
                pass


def launch_local(n: int, command: list[str], env_extra=None,
                 grace: float = 5.0) -> int:
    """One generation of n local workers; returns the job's exit code.

    Peer cleanup: ranks blocked in a collective against a dead peer never
    return, so the first *non-zero* exit terminates the survivors
    (SIGTERM -> grace -> SIGKILL) and that first bad code is propagated —
    instead of hanging until the caller's timeout. Ranks that finish with
    0 are left to drain normally.
    """
    port = free_port()
    coord = f"127.0.0.1:{port}"
    procs = [subprocess.Popen(command, env=_worker_env(r, n, coord, env_extra))
             for r in range(n)]
    first_bad = 0
    while True:
        codes = [p.poll() for p in procs]
        bad = [c for c in codes if c not in (None, 0)]
        if bad and not first_bad:
            first_bad = bad[0]
            sys.stderr.write(f"[launch] worker exited {first_bad}; "
                             "terminating peers\n")
            _terminate(procs, grace)
        if all(c is not None for c in codes):
            return _shell_code(first_bad) if first_bad else 0
        time.sleep(0.1)


def _shell_code(code: int) -> int:
    """A Popen returncode as a shell-visible exit status: signal deaths are
    negative and sys.exit would truncate them mod 256 (-9 -> 247); the
    shell convention 128+signum survives the round trip."""
    return 128 - code if code < 0 else code


class ElasticSupervisor:
    """Process-lifecycle half of elastic training (the worker half lives in
    ``mxnet_tpu.resilience.elastic``): restart crashed ranks on a re-formed
    mesh under a bounded restart budget.

    Each *generation* g gets a fresh coordinator port (the old coordinator
    died with rank 0 — reassigning the address is what lets a replacement
    world bootstrap at all) and its own heartbeat directory
    ``{hb_base}/gen-{g}`` (a dead generation's stale beat files must not
    count against the new one). The environment exported to workers is the
    :func:`mxnet_tpu.resilience.elastic.context` contract:
    ``MXNET_TPU_ELASTIC/GENERATION/ELASTIC_CAUSE/PREV_WORLD/HEARTBEAT_DIR``.

    World-size policy on a re-formation:

      - ``replace`` (default): respawn at the same world size — the lost
        rank is 1:1 replaced;
      - ``shrink``: drop the ranks that *died* (exit 75 re-formation
        requests don't shrink — those workers are healthy) down to
        ``min_workers``; the job continues on the smaller mesh, resharding
        fsdp state from the checkpoint manifest on restore. Scaling back
        *up* is a new launch at the larger ``-n`` — same manifest, same
        restore path, opposite direction.
    """

    def __init__(self, n: int, command: list[str], max_restarts: int = 3,
                 policy: str = "replace", min_workers: int = 1,
                 grace: float = 5.0, hb_dir: str | None = None,
                 poll_interval: float = 0.2, fleet_dir: str | None = None,
                 fleet_poll: float = 3.0):
        self.world = n
        self.command = command
        self.max_restarts = max_restarts
        self.policy = policy
        self.min_workers = max(1, min_workers)
        self.grace = grace
        self.poll_interval = poll_interval
        self._own_hb = hb_dir is None
        self.hb_base = hb_dir or tempfile.mkdtemp(prefix="mxtpu-elastic-hb-")
        # fleet observability (docs/OBSERVABILITY.md "Fleet view"): workers
        # snapshot per-rank telemetry here; the supervisor aggregates it on
        # a cadence and surfaces stragglers/goodput in its own log, so an
        # operator sees WHY a generation is slow before it dies
        self.fleet_dir = (fleet_dir or os.environ.get("MXNET_TPU_FLEET_DIR")
                          or os.path.join(self.hb_base, "fleet"))
        self.fleet_poll = fleet_poll
        self._fleet_agg = None  # lazily built; False = unavailable
        self._fleet_next = 0.0
        self.generation = 0
        self.reformations = 0

    def _spawn(self, cause: str, prev_world: int):
        port = free_port()
        coord = f"127.0.0.1:{port}"
        gen_hb = os.path.join(self.hb_base, f"gen-{self.generation}")
        os.makedirs(gen_hb, exist_ok=True)
        try:
            os.makedirs(self.fleet_dir, exist_ok=True)
        except OSError:
            pass
        extra = {
            "MXNET_TPU_ELASTIC": "1",
            "MXNET_TPU_GENERATION": str(self.generation),
            "MXNET_TPU_ELASTIC_CAUSE": cause,
            "MXNET_TPU_PREV_WORLD": str(prev_world),
            "MXNET_TPU_HEARTBEAT_DIR": gen_hb,
            "MXNET_TPU_FLEET_DIR": self.fleet_dir,
        }
        sys.stderr.write(
            f"[elastic] generation {self.generation}: world={self.world} "
            f"coord={coord}" + (f" cause={cause}" if cause else "") + "\n")
        return [subprocess.Popen(
            self.command, env=_worker_env(r, self.world, coord, extra))
            for r in range(self.world)]

    @staticmethod
    def _classify(code: int) -> str:
        if code == ELASTIC_RESTART_EXIT:
            return "reform_requested"
        if code < 0:
            return f"worker_killed:sig{-code}"
        return f"worker_died:exit{code}"

    def _next_world(self, n_died: int) -> int:
        if self.policy == "shrink" and n_died > 0:
            return max(self.min_workers, self.world - n_died)
        return self.world

    # -- fleet view (docs/OBSERVABILITY.md "Fleet view") ---------------------
    def _fleet_aggregator(self):
        """Lazily import the aggregator; the supervisor must keep working
        from an environment where the package cannot import (fleet
        surfacing simply turns off)."""
        if self._fleet_agg is None:
            try:
                sys.path.insert(0, os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))))
                from mxnet_tpu.observability.fleet import FleetAggregator

                self._fleet_agg = FleetAggregator(self.fleet_dir)
            except Exception as e:  # no package / no deps: disable quietly
                sys.stderr.write(f"[fleet] aggregation unavailable: {e}\n")
                self._fleet_agg = False
        return self._fleet_agg or None

    def _fleet_check(self, final: bool = False) -> None:
        """Cadenced aggregation pass: surface NEW stragglers in the
        supervisor log; on the final pass also print the goodput/MFU
        one-liner. Never raises — observability must not kill the job."""
        now = time.time()
        if not final and now < self._fleet_next:
            return
        self._fleet_next = now + self.fleet_poll
        agg = self._fleet_aggregator()
        if agg is None:
            return
        try:
            report, new = agg.poll()
        except Exception as e:
            sys.stderr.write(f"[fleet] aggregation failed: {e}\n")
            return
        for s in new:
            where = (f"gen={s.get('generation')} step={s.get('step')}"
                     if s["kind"] == "step" else "collective wait")
            sys.stderr.write(
                f"[fleet] straggler: rank={s['rank']} {where} "
                f"{s['seconds']:.3f}s vs median "
                f"{s['median_seconds']:.3f}s ({s['ratio']}x)\n")
        if final and report is not None and report.goodput is not None:
            g = report.goodput
            buckets = " ".join(
                f"{k}={v:.1f}s" for k, v in sorted(g.buckets.items())
                if v > 0)
            mfus = [r.mfu for r in report.ranks.values()
                    if r.mfu is not None]
            mfu = f" mfu={max(mfus):.4g}" if mfus else ""
            sys.stderr.write(f"[fleet] goodput={g.goodput:.3f} "
                             f"wall={g.wall:.1f}s {buckets}{mfu}\n")

    def run(self) -> int:
        try:
            return self._run()
        finally:
            self._fleet_check(final=True)
            if self._own_hb:
                shutil.rmtree(self.hb_base, ignore_errors=True)

    def _run(self) -> int:
        procs = self._spawn(cause="", prev_world=self.world)
        while True:
            self._fleet_check()
            codes = [p.poll() for p in procs]
            bad = [c for c in codes if c not in (None, 0)]
            if not bad:
                if all(c == 0 for c in codes):
                    sys.stderr.write(
                        f"[elastic] job complete: world={self.world} "
                        f"generations={self.generation + 1} "
                        f"reformations={self.reformations}\n")
                    return 0
                time.sleep(self.poll_interval)
                continue
            # a generation is over the moment one worker is gone: survivors
            # would only hang in collectives against the dead rank. A real
            # death outranks a concurrent exit-75 request for the cause
            # label — a survivor's "peer lost" exit must not mask WHY
            hard = [c for c in bad if c != ELASTIC_RESTART_EXIT]
            cause = self._classify(hard[0] if hard else bad[0])
            sys.stderr.write(f"[elastic] generation {self.generation} lost "
                             f"{len(bad)} worker(s): {cause}\n")
            _terminate(procs, self.grace)
            if self.reformations >= self.max_restarts:
                sys.stderr.write(f"[elastic] restart budget exhausted "
                                 f"({self.max_restarts}); giving up\n")
                return _shell_code(hard[0] if hard else bad[0])
            # settle: collect post-terminate exit codes to count the dead
            # (terminated survivors exit non-zero too — only the codes seen
            # BEFORE teardown count as died)
            n_died = len(hard)
            prev_world = self.world
            self.world = self._next_world(n_died)
            self.generation += 1
            self.reformations += 1
            procs = self._spawn(cause=cause, prev_world=prev_world)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="accepted for reference compat; there is no server "
                         "role (state is sharded with workers)")
    ap.add_argument("--launcher", choices=["local", "ssh"], default="local")
    ap.add_argument("-H", "--hostfile", default=None)
    ap.add_argument("--elastic", action="store_true",
                    help="supervise workers: re-form the mesh on worker "
                         "loss instead of failing the job")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="elastic restart budget: mesh re-formations before "
                         "the supervisor gives up")
    ap.add_argument("--elastic-policy", choices=["replace", "shrink"],
                    default="replace",
                    help="replace: respawn at the same world size; shrink: "
                         "continue on a smaller mesh without the dead ranks")
    ap.add_argument("--min-workers", type=int, default=1,
                    help="floor for --elastic-policy shrink")
    ap.add_argument("--grace", type=float, default=5.0,
                    help="seconds between SIGTERM and SIGKILL at teardown")
    ap.add_argument("--fleet-dir", default=None,
                    help="shared fleet-telemetry directory exported to "
                         "workers as MXNET_TPU_FLEET_DIR (default: env "
                         "value, else a dir beside the heartbeat base); "
                         "the supervisor aggregates it and logs stragglers")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")
    if args.launcher == "local":
        why = shared_chip_refusal(
            args.num_workers, os.environ,
            sorted(glob.glob("/dev/accel[0-9]*")
                   + glob.glob("/dev/vfio/[0-9]*")))
        if why:
            ap.error(why)
        if args.elastic:
            sup = ElasticSupervisor(
                args.num_workers, args.command,
                max_restarts=args.max_restarts, policy=args.elastic_policy,
                min_workers=args.min_workers, grace=args.grace,
                fleet_dir=args.fleet_dir)
            sys.exit(sup.run())
        sys.exit(launch_local(args.num_workers, args.command,
                              grace=args.grace))
    if args.elastic:
        ap.error("--elastic requires --launcher local (the supervisor owns "
                 "the worker process tree)")
    # ssh plan (zero-egress: print what would run per host)
    hosts = open(args.hostfile).read().split() if args.hostfile else ["host%d" % i for i in range(args.num_workers)]
    port = free_port()
    for rank, host in enumerate(hosts[: args.num_workers]):
        print(f"ssh {host} MXNET_TPU_COORDINATOR={hosts[0]}:{port} "
              f"MXNET_TPU_NPROC={args.num_workers} MXNET_TPU_PROCID={rank} "
              + " ".join(args.command))


if __name__ == "__main__":
    main()
