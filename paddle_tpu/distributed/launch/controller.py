"""Collective controller: build per-process env, deploy, watch, restart.

Reference parity: python/paddle/distributed/launch/controllers/collective.py
(:22 CollectiveController.build_pod) + watcher.py (:22 Watcher). The env
contract matches parallel_env.py: PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM /
PADDLE_MASTER (+ MASTER_ADDR/PORT), so a launched script's
init_parallel_env() lands on jax.distributed.initialize. TPU-native default:
one process per node (nproc_per_node=1) — the controller process drives all
local chips; the reference's one-proc-per-GPU shape is still available for
CPU-mesh testing via --nproc_per_node.
"""
from __future__ import annotations

import json
import os
import socket
import sys
import time

from ..resilience.retry import backoff_delay
from .job import Pod
from .master import HTTPMaster

RESTART_BACKOFF_CAP_S = 30.0


def _launch_metric(name: str, doc: str) -> None:
    from ... import telemetry as _tm

    if _tm.enabled():
        _tm.counter(name, doc).inc()


class Context:
    def __init__(self, args):
        self.args = args

    def is_master_host(self, host):
        try:
            return host in ("127.0.0.1", "localhost", socket.gethostname(), socket.gethostbyname(socket.gethostname()))
        except Exception:
            return host in ("127.0.0.1", "localhost")


class CollectiveController:
    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.pod = Pod()
        self.master = None
        self.elastic = None  # ElasticManager when elastic mode is on
        self.elastic_restarts = 0
        # restart backoff state: consecutive restarts since the last healthy
        # window, and when the last restart happened (monotonic)
        self.consecutive_restarts = 0
        self.last_restart_t = None

    # ---- topology ----
    def _rendezvous(self):
        args = self.ctx.args
        if args.nnodes <= 1:
            return 0
        self.master = HTTPMaster(self.ctx)
        endpoint = f"{socket.gethostname()}:{os.getpid()}"
        _, node_rank = self.master.sync_peers(args.job_id, endpoint, args.nnodes)
        return node_rank

    def build_pod(self):
        args = self.ctx.args
        node_rank = args.node_rank if args.node_rank is not None else self._rendezvous()
        nproc = args.nproc_per_node
        world = args.nnodes * nproc
        if args.master:
            coord = args.master.replace("http://", "")
        else:
            coord = f"127.0.0.1:{args.port}"
        for local_rank in range(nproc):
            rank = node_rank * nproc + local_rank
            env = {
                "PADDLE_TRAINER_ID": str(rank),
                "PADDLE_TRAINERS_NUM": str(world),
                "PADDLE_LOCAL_RANK": str(local_rank),
                "PADDLE_LOCAL_SIZE": str(nproc),
                "PADDLE_NNODES": str(args.nnodes),
                "PADDLE_MASTER": coord,
                "MASTER_ADDR": coord.rsplit(":", 1)[0],
                "MASTER_PORT": coord.rsplit(":", 1)[1],
                "PADDLE_JOB_ID": args.job_id,
            }
            if args.devices:
                env["TPU_VISIBLE_DEVICES"] = args.devices
                env["CUDA_VISIBLE_DEVICES"] = args.devices
            out = os.path.join(args.log_dir, f"workerlog.{rank}") if args.log_dir else None
            entry = [sys.executable, "-u"] + ([args.training_script] if not args.module else ["-m", args.training_script])
            self.pod.add_container(entry + list(args.training_script_args), env, out)
        return self.pod

    # ---- run + watch ----
    def run(self):
        self.build_pod()
        self.pod.deploy()
        code = self.watch()
        if self.master:
            self.master.stop()
        return code

    # ---- elastic (reference fleet/elastic/manager.py:124) ----
    def enable_elastic(self, manager):
        """Attach an ElasticManager: the watch loop consumes its scale
        events, re-ranks and relaunches the pod on membership change."""
        self.elastic = manager
        # beat several times per staleness window or we age ourselves out
        manager.register(interval=min(3.0, manager.timeout / 3.0))

    def _elastic_restart(self):
        """Membership changed: recompute node rank/world from the alive set
        and relaunch every local worker with re-ranked envs (the reference's
        scale-event -> relaunch-with-new-ranks flow).

        Elastic restarts spend the SAME jittered-backoff/budget accounting
        as pod restarts (_apply_restart_backoff): a node flapping in and out
        of the membership set would otherwise relaunch the pod in a tight
        loop with an unmetered budget. Returns False when the restart budget
        is exhausted (the watch loop then tears down) or this node fell out
        of the alive set."""
        nodes = self.elastic.alive_nodes()
        if self.elastic.host not in nodes:
            return False
        args = self.ctx.args
        if args.max_restart > 0 and self.consecutive_restarts >= args.max_restart:
            print(
                f"[launch] elastic: restart budget exhausted "
                f"({self.consecutive_restarts}/{args.max_restart} since last "
                "healthy window), giving up",
                file=sys.stderr,
            )
            return False
        prev_world = args.nnodes * args.nproc_per_node
        args.nnodes = len(nodes)
        args.node_rank = nodes.index(self.elastic.host)
        self.elastic.np = len(nodes)
        new_world = args.nnodes * args.nproc_per_node
        # the largest valid mesh over the survivors: degrees come from
        # PADDLE_ELASTIC_DEGREES on the controller (JSON, e.g. '{"tp":2}');
        # the plan is exported to every relaunched worker so fleet.init
        # lands on the mesh reshard-on-load targets
        try:
            degrees = json.loads(os.environ.get("PADDLE_ELASTIC_DEGREES", "{}"))
            if not isinstance(degrees, dict):
                raise TypeError(f"expected a JSON object, got {type(degrees).__name__}")
        except Exception as e:
            print(
                f"[launch] unusable PADDLE_ELASTIC_DEGREES "
                f"({type(e).__name__}: {e}) — planning with tp=pp=1",
                file=sys.stderr,
            )
            degrees = {}
        # plan from the SAME membership snapshot the re-rank above used —
        # a fresh query could disagree if another node died meanwhile
        plan = self.elastic.plan_world(args.nproc_per_node, degrees, nodes=nodes)
        print(
            f"[launch] elastic scale event: nodes={nodes} -> re-rank "
            f"node_rank={args.node_rank} world={new_world} "
            f"mesh plan={plan}",
            file=sys.stderr,
        )
        _launch_metric(
            "paddle_tpu_launch_elastic_restarts_total",
            "pod relaunches from elastic membership changes",
        )
        try:
            from ...telemetry import timeline as _tl

            _tl.emit("elastic", "restart_plan", severity="warn",
                     nodes=len(nodes), node_rank=int(args.node_rank),
                     prev_world=int(prev_world), new_world=int(new_world),
                     plan=dict(plan) if isinstance(plan, dict) else plan)
        except Exception:
            pass
        self.pod.stop(force=True)
        self._apply_restart_backoff()
        self.pod = Pod()
        self.build_pod()
        reshard_env = {
            "PADDLE_ELASTIC_RESTARTS": str(self.elastic_restarts + 1),
            "PADDLE_ELASTIC_PREV_WORLD": str(prev_world),
            "PADDLE_ELASTIC_PLAN": json.dumps(plan),
        }
        for c in self.pod.containers:
            c.env.update(reshard_env)
        self.pod.deploy()
        self.elastic_restarts += 1
        return True

    # ---- restart budget + backoff ----
    def _apply_restart_backoff(self) -> None:
        """The shared jittered-backoff accounting: sleep the doubling
        full-jitter delay, then count this restart against the budget that
        _maybe_reset_restart_budget returns after a healthy window."""
        base = getattr(self.ctx.args, "restart_backoff", 0.5)
        if base > 0:
            delay = backoff_delay(self.consecutive_restarts, base, RESTART_BACKOFF_CAP_S)
            print(f"[launch] restart backoff {delay:.2f}s "
                  f"(consecutive={self.consecutive_restarts + 1})", file=sys.stderr)
            time.sleep(delay)
        self.consecutive_restarts += 1
        self.last_restart_t = time.monotonic()

    def _restart_pod(self, why: str) -> None:
        """Terminate + reap every container, back off, redeploy.

        Restarting the WHOLE pod, not just the dead rank: a collective job's
        survivors are blocked on the dead peer (the reference's NCCL jobs
        behave the same — watchdog aborts the peers, launcher redeploys all);
        workers resume from their distributed checkpoint. The backoff doubles
        per consecutive restart with full jitter so a crash-looping pod
        doesn't burn its restart budget racing zombies (or a half-restarted
        master), and decorrelates multi-node redeploy stampedes."""
        print(f"[launch] {why}, restarting pod", file=sys.stderr)
        _launch_metric("paddle_tpu_launch_restarts_total", "pod restarts by the launch controller")
        for c in self.pod.containers:
            c.terminate(force=True)
            c.restarts += 1
        # reap before redeploy: a dying worker can still hold the exclusive
        # device lock, and an unreaped Popen is a zombie
        for c in self.pod.containers:
            c.wait(timeout=10)
        self._apply_restart_backoff()
        self.pod.deploy()

    def _maybe_reset_restart_budget(self) -> None:
        """A pod that has run clean for the healthy window earns its restart
        budget back — a preemption every few hours must not accumulate
        toward --max_restart forever."""
        window = getattr(self.ctx.args, "restart_healthy_window", 0.0)
        if (
            window > 0
            and self.last_restart_t is not None
            and time.monotonic() - self.last_restart_t >= window
            and not self.pod.failed_containers()
        ):
            print(
                f"[launch] pod healthy for {window:.0f}s: restart budget reset",
                file=sys.stderr,
            )
            _launch_metric(
                "paddle_tpu_launch_budget_resets_total",
                "restart budgets returned after a healthy window",
            )
            for c in self.pod.containers:
                c.restarts = 0
            self.consecutive_restarts = 0
            self.last_restart_t = None

    def watch(self) -> int:
        """Poll container status (reference watcher.py): on failure either
        restart the whole pod (elastic, up to max_restart) or tear down."""
        from ..fleet.elastic.manager import ElasticStatus

        args = self.ctx.args
        while True:
            time.sleep(args.poll_interval)
            self._maybe_reset_restart_budget()
            if self.elastic is not None:
                st = self.elastic.watch()
                if st == ElasticStatus.RESTART:
                    if self._elastic_restart():
                        continue
                    self.pod.stop(force=True)
                    return 2
                if st == ElasticStatus.EXIT:
                    print("[launch] elastic: this node aged out, exiting", file=sys.stderr)
                    self.pod.stop(force=True)
                    return 2
            if not self.pod.is_running():
                failed = self.pod.failed_containers()
                if not failed:
                    return 0
                if args.max_restart > 0 and all(c.restarts < args.max_restart for c in self.pod.containers):
                    self._restart_pod(f"{len(failed)} container(s) failed")
                    continue
                print(f"[launch] job failed: exit codes {self.pod.exit_codes()}", file=sys.stderr)
                return 1
            failed = self.pod.failed_containers()
            if failed:
                restartable = args.max_restart > 0 and all(c.restarts < args.max_restart for c in failed)
                if restartable:
                    self._restart_pod(
                        f"rank(s) {[c.env['PADDLE_TRAINER_ID'] for c in failed]} failed"
                    )
                else:
                    print("[launch] container failed, stopping pod", file=sys.stderr)
                    self.pod.stop(force=True)
                    return 1
