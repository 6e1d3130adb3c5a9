"""The pjit-ed train step factory — the performance path.

One compiled XLA program = forward + backward + (GSPMD-inserted) gradient
all-reduce + optimizer update, with donated buffers. This is the TPU
replacement for the whole per-batch choreography of SURVEY §3.2 (CachedOp
forward, autograd backward, KVStore push/pull, per-param optimizer ops).

Works with any Gluon ``HybridBlock``: parameters are pulled into a pytree,
the block's forward is re-run functionally inside jit via the hybrid trace
machinery, and updated parameters are written back on request (``sync``).
"""
from __future__ import annotations

import collections
import math
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import observability as _obs
from .. import random as _rng
from ..observability import profiling as _profiling
from ..gluon.block import _HybridTrace
from ..ndarray import NDArray
from .sharding import ShardingRules

__all__ = ["TrainStep"]

#: With telemetry on, step n's loss, gradient norm and loss scale are read
#: from the device this many dispatches later (or at ``obs.flush()``), so the
#: reading never waits for the step just dispatched. Of the order of the
#: runtime's own queue (the TPU runtime lets about 8 steps be queued).
TELEMETRY_LAG = 8

# one dispatch whose readings telemetry still holds as device futures
_Held = collections.namedtuple(
    "_Held", "loop step t_entry values window accum samples tokens flops")


def _programs_held(jitted) -> int:
    """Executables in a jitted function's own cache: one more after a call
    than before it means that call lowered or compiled."""
    size = getattr(jitted, "_cache_size", None)
    return size() if size is not None else 0


class TrainStep:
    """Compile a full training step over a mesh.

    Parameters
    ----------
    net : HybridBlock — the model (initialized).
    loss_fn : callable(out_nd, *label_nds) -> scalar-able NDArray loss.
    optimizer : mxnet_tpu.optimizer.Optimizer (pure update_raw protocol).
    mesh : jax.sharding.Mesh or None (single device).
    rules : ShardingRules for parameters (None = replicate).
    batch_spec : PartitionSpec for each batch input (default shard dim0 on
        'dp' when the mesh has that axis).
    donate : donate param/opt-state buffers (default True).
    amp : compiled-in mixed-precision policy — ``"auto"`` (default)
        inherits the global ``contrib.amp.init`` dtype, ``"bfloat16"`` /
        ``"float16"`` / a ``contrib.amp.Policy`` force one, ``None``
        disables. Float32 params and model inputs are cast to the compute
        dtype INSIDE the jitted program (XLA fuses the casts away; every
        matmul lowers to a low-precision dot) while the stored params — the
        fp32 master weights — and the optimizer update stay float32. Under
        ``float16`` the dynamic loss scale rides the compiled carry:
        overflow is a compiled isfinite-all-reduce feeding a ``lax.cond``
        skip-update, no host sync, window-compatible. ``num_update`` counts
        attempted steps; the compiled ``step_count`` (Adam's t) advances
        only on applied ones.
    """

    def __init__(self, net, loss_fn, optimizer, mesh: Optional[Mesh] = None,
                 rules: Optional[ShardingRules] = None, batch_spec=None,
                 donate: bool = True, n_model_inputs: int = 1, amp="auto",
                 layout: Optional["Layout"] = None):
        from ..contrib.amp import resolve_policy
        from .layout import Layout

        self.amp_policy = resolve_policy(amp)
        self.net = net
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.n_model_inputs = n_model_inputs
        # the declarative layout (docs/PARALLELISM.md) is the ONE source
        # of truth: mesh, rules and batch placement all derive from it.
        # The legacy (mesh=, rules=) convention still works and is
        # bridged INTO a Layout, so cache keys, checkpoint manifests and
        # the audit pipeline see one spec either way.
        if layout is not None:
            if mesh is not None or rules is not None:
                raise ValueError("pass layout= OR (mesh=, rules=), "
                                 "not both")
            if layout.total > 1:
                mesh = layout.mesh()
            rules = layout.sharding_rules()
            if batch_spec is None and layout.batch_axes:
                batch_spec = layout.batch_spec()
        self.mesh = mesh
        self.rules = rules or ShardingRules()
        if layout is None:
            try:
                layout = (Layout.from_mesh(mesh, self.rules, batch_spec)
                          if mesh is not None else Layout())
            except ValueError:
                layout = None  # mesh outside the AXES vocabulary
        self.layout = layout
        # async gradient-collective overlap (layout policy): bucketed
        # barrier hints in the program
        self._overlap_on = bool(layout is not None and layout.overlap
                                and mesh is not None)
        self.donate = donate
        self._plist = [p for _, p in sorted(net.collect_params().items())]
        for p in self._plist:
            if p._nd is None:
                raise ValueError(f"parameter {p.name} not initialized; run one "
                                 "forward pass first")
        self._trainable = [p.grad_req != "null" for p in self._plist]
        self.params = {p.name: p._nd._data for p in self._plist}
        self.opt_state = {
            p.name: optimizer.create_state(i, p._nd._data)
            for i, p in enumerate(self._plist) if self._trainable[i]
        }
        self.step_count = jnp.zeros((), jnp.int32)
        # fp16 dynamic loss scaling: compiled carry (docs/PERFORMANCE.md).
        # bf16 shares f32's exponent range, so only float16 gets a scale.
        if self.amp_policy is not None and self.amp_policy.dynamic_scaling:
            self.amp_state = {
                "scale": jnp.float32(self.amp_policy.loss_scale),
                "good": jnp.int32(0),
                "skipped": jnp.int32(0),
            }
        else:
            self.amp_state = None
        self._amp_skipped_seen = 0  # host mirror for the telemetry counter
        self._compute_specs = {}
        if mesh is not None:
            specs = self.rules.tree_specs(self.params, mesh)
            self.param_sharding = {k: NamedSharding(mesh, s) for k, s in specs.items()}
            # compute spec = storage spec minus the fsdp (ZeRO) axis; only
            # params whose spec actually differs get a gather constraint
            fsdp_ax = self.rules.fsdp_axis
            if fsdp_ax is not None:
                for k, s in specs.items():
                    centries = []
                    for e in tuple(s):
                        if e == fsdp_ax:
                            centries.append(None)
                        elif isinstance(e, tuple):
                            kept = tuple(a for a in e if a != fsdp_ax)
                            centries.append(kept if kept else None)
                        else:
                            centries.append(e)
                    if tuple(centries) != tuple(s):
                        self._compute_specs[k] = P(*centries)
            self.params = {k: jax.device_put(v, self.param_sharding[k])
                           for k, v in self.params.items()}
            self.opt_state = jax.tree_util.tree_map(
                lambda x: x, self.opt_state)  # states follow params lazily below
            self.opt_state = {
                k: jax.tree_util.tree_map(
                    lambda s, _k=k: jax.device_put(s, self.param_sharding[_k]), v)
                for k, v in self.opt_state.items()
            }
            if batch_spec is None and "dp" in mesh.shape:
                axes = [ax for ax in ("dp", "fsdp") if ax in mesh.shape and mesh.shape[ax] > 1]
                batch_spec = P(tuple(axes) if len(axes) > 1 else (axes[0] if axes else None))
            self.batch_sharding = NamedSharding(mesh, batch_spec or P())
            self.step_count, self.amp_state = self._on_mesh(
                (self.step_count, self.amp_state))
        else:
            self.param_sharding = None
            self.batch_sharding = None
        # graceful preemption (resilience subsystem): set by install_preemption
        self._preempt_guard = None
        self._preempt_dir = None
        self._preempt_exit = True
        # jit cache keyed on (batch arity, resolved lr/wd multipliers,
        # telemetry flag): the in_shardings tuple built by _make_step depends
        # on how many batch arrays the call passes, the multipliers fold into
        # the program as constants, and telemetry adds a grad-norm output —
        # any of them changing needs its own jitted program
        self._compiled: Dict[tuple, Callable] = {}
        # recompile detection (observability + analysis subsystems): every
        # program fingerprint (shapes, dtypes, static args) seen so far — a
        # miss means XLA is about to lower+compile a new executable, which
        # fused execution otherwise hides completely. The guard diffs the
        # new fingerprint against the closest seen one, so the event log
        # carries the recompile *cause* ("shape"/"dtype"/"hyperparams"),
        # not just a count (docs/ANALYSIS.md).
        from ..analysis import RecompileGuard

        self._recompile_guard = RecompileGuard(
            "train_recompiles_total",
            "TrainStep program lowerings (cache misses)",
            # historical label names: static-arg changes (lr/wd multiplier
            # edits, batch arity) have always counted as "hyperparams"
            label_map={"static": "hyperparams", "arity": "hyperparams"})
        self._monitors: list = []
        # analytic model-FLOPs memo (observability.goodput, keyed by the
        # jit cache key): feeds the train_model_flops_per_step / train_mfu
        # gauges without re-lowering on every recorded step
        self._flops_cache: Dict[tuple, Optional[float]] = {}
        # attached DevicePrefetcher (io.prefetch): batches arrive already
        # device-resident + sharded, so __call__/run skip the per-call
        # device_put on the caller thread
        self._prefetcher = None
        # window-program dispatch count — tests assert one dispatch per
        # window
        self._window_dispatches = 0
        # telemetry-on readings not yet published (_hold/_publish), and
        # when the host last saw a dispatch done
        self._held: collections.deque = collections.deque()
        self._seen_done = 0.0
        _obs.on_flush(self.flush_telemetry)

    def _on_mesh(self, tree):
        """Place the scalar carry (step count, amp state) replicated on the
        mesh, as the step program returns it. jax types an array by the
        mesh it lives on: a carry created off the mesh makes the SECOND
        call a new signature — a full retrace and compile (17 s of a
        BERT-large four-chip run even with the persistent cache warm)."""
        if self.mesh is None:
            return tree
        rep = NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, rep), tree)

    # -- functional loss -----------------------------------------------------
    def _loss_of(self, params: Dict[str, jax.Array], batch, key):
        from .._mesh_state import active_mesh

        raws = [params[p.name] for p in self._plist]
        n = self.n_model_inputs
        # the active mesh lets _sharding_constraint ops in model/loss code
        # pin layouts at known dp→tp transition points (MLM head)
        with active_mesh(self.mesh), _HybridTrace(self._plist, raws, True, key):
            nd_batch = [NDArray(b) for b in batch]
            with jax.named_scope("forward"):
                out = self.net(*nd_batch[:n])
            with jax.named_scope("loss"):
                loss = self.loss_fn(out, *nd_batch[n:])
                raw = loss._data if isinstance(loss, NDArray) else loss
                return jnp.mean(raw.astype(jnp.float32))

    def _resolve_mults(self):
        """Static per-name lr/wd multipliers, resolving the same channels as
        Optimizer._get_lr/_get_wd (Parameter attrs, opt.set_lr_mult/
        set_wd_mult, opt.param_dict) so TrainStep and the imperative Trainer
        freeze/scale the same parameters. Snapshot at compile time — the
        multipliers fold into the jitted program as constants."""
        opt = self.optimizer
        lr_mult, wd_mult = {}, {}
        for p in self._plist:
            # mirror Optimizer._get_lr exactly: the param_dict entry (when
            # present) REPLACES the Parameter as the attribute source, then
            # the name-keyed set_lr_mult dict multiplies on top
            src = opt.param_dict.get(p.name, p)
            lm = float(getattr(src, "lr_mult", 1.0))
            wm = float(getattr(src, "wd_mult", 1.0))
            lr_mult[p.name] = lm * float(opt.lr_mult.get(p.name, 1.0))
            wd_mult[p.name] = wm * float(opt.wd_mult.get(p.name, 1.0))
        return lr_mult, wd_mult

    def _amp_cast(self, params, batch):
        """Cast f32 params + f32 MODEL inputs (not labels) to the policy's
        compute dtype — called inside the traced loss, so the casts fuse
        into the surrounding ops and grads flow back f32 to the masters."""
        pol = self.amp_policy
        if pol is None:
            return params, batch
        cd = pol.jnp_compute_dtype
        with jax.named_scope("amp"):
            params = {k: (v.astype(cd) if v.dtype == jnp.float32 else v)
                      for k, v in params.items()}
            n = self.n_model_inputs
            batch = tuple(
                b.astype(cd) if (i < n and hasattr(b, "dtype")
                                 and b.dtype == jnp.float32) else b
                for i, b in enumerate(batch))
        return params, batch

    def _grad_fn(self):
        """``value_and_grad`` of the ZeRO-aware loss, shared by the
        single-step and window programs.

        ZeRO compute/storage split: fsdp-sharded params are explicitly
        all-gathered for compute (constraint to the fsdp-free spec); the
        constraint's transpose reduce-scatters the grads back to the
        storage layout. Without this GSPMD may instead compute weight grads
        in the storage layout, forcing an involuntary full remat of the
        activation cotangent (round-3 MULTICHIP tail warning).

        With an AMP policy the f32 masters are cast to the compute dtype
        here, INSIDE the differentiated function: grads come back f32 (the
        cast's transpose) while every model matmul runs low-precision.
        ``scale`` (float16 dynamic loss scaling) multiplies the f32 loss —
        the caller unscales grads and loss by 1/scale."""
        def lossf(p, batch, key, scale=None):
            cp = dict(p)
            for name, cspec in self._compute_specs.items():
                cp[name] = jax.lax.with_sharding_constraint(
                    p[name], NamedSharding(self.mesh, cspec))
            cp, batch = self._amp_cast(cp, batch)
            loss = self._loss_of(cp, batch, key)
            if scale is not None:
                with jax.named_scope("amp"):
                    loss = loss * scale
            return loss

        return jax.value_and_grad(lossf)

    def _overlap_grads(self, grads):
        """Bucketed async-collective hint (layout ``overlap`` policy,
        arXiv:2004.13336): group the gradient dict into
        ``layout.overlap_buckets`` buckets and chain each bucket's grads
        behind a representative of the NEXT bucket with
        ``lax.optimization_barrier``. The barrier is the identity on
        values but adds a scheduling edge: a bucket's optimizer update
        cannot be hoisted before the next bucket's gradients exist, so a
        latency-hiding backend keeps each bucket's reduce-scatter/
        all-reduce in flight while later backprop still computes.
        (XLA's CPU backend expands the barrier away after SPMD
        partitioning; on TPU it constrains the scheduler.)"""
        if not self._overlap_on or len(grads) < 2:
            return grads
        names = sorted(grads)
        k = min(self.layout.overlap_buckets, len(names))
        if k < 2:
            return grads
        size = -(-len(names) // k)
        buckets = [names[i:i + size] for i in range(0, len(names), size)]
        out = dict(grads)
        for i in range(len(buckets) - 1):
            rep = grads[buckets[i + 1][0]]  # pre-barrier: no chain cycles
            tied = jax.lax.optimization_barrier(
                tuple(out[n] for n in buckets[i]) + (rep,))
            for n, v in zip(buckets[i], tied[:-1]):
                out[n] = v
        return out

    def _apply_update(self, params, opt_state, t, grads, lr, wd,
                      lr_mult, wd_mult):
        """One optimizer application over the whole param dict (traced)."""
        grads = self._overlap_grads(grads)
        opt = self.optimizer
        new_params, new_state = dict(params), {}
        with jax.named_scope("optimizer"):
            for name in params:
                if name not in opt_state:
                    continue
                nw, ns = opt.update_raw(
                    params[name], grads[name], opt_state[name],
                    lr * lr_mult.get(name, 1.0),
                    wd * wd_mult.get(name, 1.0), t)
                new_params[name] = nw
                new_state[name] = ns
        return new_params, new_state

    @staticmethod
    def _grad_norm(grads, names):
        """Global gradient norm for telemetry: a handful of fused reduces,
        compiled into the program only when telemetry is on."""
        with jax.named_scope("grad_norm"):
            return jnp.sqrt(sum(
                jnp.sum(jnp.square(grads[n].astype(jnp.float32)))
                for n in names))

    def _opt_shardings(self):
        return {
            k: jax.tree_util.tree_map(lambda _: self.param_sharding[k], v)
            for k, v in self.opt_state.items()}

    def _next_amp_state(self, amp_state, finite):
        """Compiled dynamic-loss-scale transition (reference LossScaler
        semantics, in-graph): overflow halves the scale (floor 1.0) and
        resets the good-step run; ``scale_window`` consecutive good steps
        double it."""
        pol = self.amp_policy
        scale = amp_state["scale"]
        good = jnp.where(finite, amp_state["good"] + 1, 0)
        grow = good >= pol.scale_window
        new_scale = jnp.where(
            finite,
            jnp.where(grow, scale * pol.scale_factor, scale),
            jnp.maximum(scale / pol.scale_factor, 1.0))
        return {"scale": new_scale.astype(jnp.float32),
                "good": jnp.where(grow, jnp.int32(0), good).astype(jnp.int32),
                "skipped": amp_state["skipped"]
                + jnp.logical_not(finite).astype(jnp.int32)}

    @staticmethod
    def _finite_all(grads, names):
        """One fused finiteness reduction over every trainable grad — the
        compiled replacement for LossScaler.has_overflow's per-param loop."""
        ok = jnp.asarray(True)
        for n in names:
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(grads[n])))
        return ok

    def _scaled_update(self, params, opt_state, step_count, amp_state, grads,
                      sloss, lr, wd, lr_mult, wd_mult):
        """Unscale grads, gate the optimizer update on finiteness via
        ``lax.cond`` (skip = identity carry, Adam's t frozen), advance the
        amp carry. Shared by the single-step and window programs."""
        with jax.named_scope("amp"):
            inv = 1.0 / amp_state["scale"]
            grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
            loss = sloss * inv
            finite = self._finite_all(grads, list(opt_state))
        t2 = step_count + 1

        def _apply(_):
            np_, ns = self._apply_update(params, opt_state, t2, grads, lr,
                                         wd, lr_mult, wd_mult)
            return np_, ns, t2

        def _skip(_):
            return dict(params), dict(opt_state), step_count

        new_params, new_state, new_t = jax.lax.cond(finite, _apply, _skip,
                                                    None)
        with jax.named_scope("amp"):
            new_amp = self._next_amp_state(amp_state, finite)
        return new_params, new_state, new_t, new_amp, grads, loss

    def _step_cache_key(self, n_raws, obs_on):
        """Jit-cache key of the single-step program: everything folded into
        the compiled program as a constant (batch arity, lr/wd multiplier
        snapshots, the telemetry grad-norm output). ONE constructor —
        ``__call__`` and ``lower_hlo``/``audit()`` must build the identical
        key, or audits would inspect a different program than the one
        production dispatches."""
        lr_mult, wd_mult = self._resolve_mults()
        return (n_raws, tuple(sorted(lr_mult.items())),
                tuple(sorted(wd_mult.items())), obs_on)

    def _window_cache_key(self, window, accum, n_raws, obs_on):
        """Jit-cache key of the fused k-step window program — shared by
        ``_run_window`` and ``lower_window_hlo`` for the same reason as
        :meth:`_step_cache_key`."""
        n, lr_t, wd_t, o = self._step_cache_key(n_raws, obs_on)
        return ("window", window, accum, n, lr_t, wd_t, o)

    def _make_step(self, n_batch, with_gnorm=False):
        lr_mult, wd_mult = self._resolve_mults()
        grad_fn = self._grad_fn()
        scaling = self.amp_state is not None

        def step(params, opt_state, step_count, batch, key, lr, wd):
            loss, grads = grad_fn(params, batch, key)
            t = step_count + 1
            new_params, new_state = self._apply_update(
                params, opt_state, t, grads, lr, wd, lr_mult, wd_mult)
            if with_gnorm:
                return (new_params, new_state, t, loss,
                        self._grad_norm(grads, opt_state))
            return new_params, new_state, t, loss

        def step_scaled(params, opt_state, step_count, amp_state, batch, key,
                        lr, wd):
            sloss, grads = grad_fn(params, batch, key, amp_state["scale"])
            (new_params, new_state, new_t, new_amp, grads,
             loss) = self._scaled_update(params, opt_state, step_count,
                                         amp_state, grads, sloss, lr, wd,
                                         lr_mult, wd_mult)
            if with_gnorm:
                return (new_params, new_state, new_t, new_amp, loss,
                        self._grad_norm(grads, opt_state))
            return new_params, new_state, new_t, new_amp, loss

        fn = step_scaled if scaling else step
        donate = (0, 1) if self.donate else ()
        if self.mesh is not None:
            opt_shardings = self._opt_shardings()
            rep = NamedSharding(self.mesh, P())
            in_shardings = (
                self.param_sharding,
                opt_shardings,
                rep,
            ) + ((rep,) if scaling else ()) + (
                tuple(self.batch_sharding for _ in range(n_batch)),
                rep, rep, rep,
            )
            # pin outputs to the storage layout: without this the ZeRO
            # compute-gather lets GSPMD return some updated params gathered,
            # silently growing per-device memory across steps
            out_shardings = (
                self.param_sharding,
                opt_shardings,
                rep,
            ) + ((rep,) if scaling else ()) + (rep,)
            if with_gnorm:
                out_shardings = out_shardings + (rep,)
            return jax.jit(fn, donate_argnums=donate,
                           in_shardings=in_shardings,
                           out_shardings=out_shardings)
        return jax.jit(fn, donate_argnums=donate)

    def window_batch_sharding(self, accum: int = 1):
        """Sharding for a window-stacked batch array: the per-step batch
        spec shifted right by the leading [window] (and [accum]) dims."""
        if self.batch_sharding is None:
            return None
        nlead = 2 if accum > 1 else 1
        return NamedSharding(
            self.mesh, P(*((None,) * nlead + tuple(self.batch_sharding.spec))))

    def _make_window(self, n_batch, window, accum, with_gnorm=False):
        """ONE jitted program for ``window`` consecutive steps: a
        ``jax.lax.scan`` whose carry (params / opt-state / step-count) is
        donated and whose per-step losses come back as a stacked future —
        forward+backward+update xK with zero per-step Python or dispatch
        (the 'one program per window' extension of the per-step fusion
        thesis; docs/PERFORMANCE.md).

        With ``accum`` > 1 each scan step consumes ``accum`` stacked
        microbatches: gradients are accumulated in the fsdp *storage*
        layout (Xu et al. 2020 — accumulate sharded, never gathered) and
        the optimizer applies the mean once per step.

        Under a float16 AMP policy the dynamic loss scale rides the scan
        carry: each in-window step scales its loss, checks finiteness, and
        conditionally skips its update — no host sync anywhere in the
        window, the contract the host-side LossScaler could never meet."""
        lr_mult, wd_mult = self._resolve_mults()
        grad_fn = self._grad_fn()
        scaling = self.amp_state is not None

        def _grads_of(p, batch, key, scale):
            """(loss, grads) for one step — single batch or accum stack."""
            if accum == 1:
                return grad_fn(p, batch, key, scale)

            def constrain(g):
                if self.mesh is None:
                    return g
                return {k: (jax.lax.with_sharding_constraint(
                                v, self.param_sharding[k])
                            if k in self.param_sharding else v)
                        for k, v in g.items()}

            # the "accumulate" scope names the accumulation's own
            # arithmetic; each microbatch's forward and backward keep theirs
            def micro(acc, mxs):
                mb, midx = mxs
                l, g = grad_fn(p, mb, jax.random.fold_in(key, midx), scale)
                with jax.named_scope("accumulate"):
                    return (acc[0] + l,
                            jax.tree_util.tree_map(
                                jnp.add, acc[1], constrain(g))), None

            with jax.named_scope("accumulate"):
                zeros = constrain(
                    {k: jnp.zeros(v.shape, v.dtype) for k, v in p.items()})
            (lsum, gsum), _ = jax.lax.scan(
                micro, (jnp.float32(0.0), zeros),
                (batch, jnp.arange(accum)))
            with jax.named_scope("accumulate"):
                return lsum / accum, jax.tree_util.tree_map(
                    lambda x: x / accum, gsum)

        def window_fn(params, opt_state, step_count, batches, keys, lrs, wd):
            # lrs is a [window] vector scanned alongside the batches: with
            # an lr_scheduler each step i trains at scheduler(num_update+i),
            # exactly what i sequential __call__s would read
            def body(carry, xs):
                p, s, t = carry
                batch, key, lr = xs
                loss, grads = _grads_of(p, batch, key, None)
                t2 = t + 1
                np_, ns = self._apply_update(p, s, t2, grads, lr, wd,
                                             lr_mult, wd_mult)
                if with_gnorm:
                    return (np_, ns, t2), (loss, self._grad_norm(grads, s))
                return (np_, ns, t2), loss

            carry, ys = jax.lax.scan(
                body, (params, opt_state, step_count),
                (tuple(batches), keys, lrs))
            params, opt_state, t = carry
            if with_gnorm:
                losses, gnorms = ys
                return params, opt_state, t, losses, gnorms
            return params, opt_state, t, ys

        def window_scaled(params, opt_state, step_count, amp_state, batches,
                          keys, lrs, wd):
            def body(carry, xs):
                p, s, t, a = carry
                batch, key, lr = xs
                sloss, grads = _grads_of(p, batch, key, a["scale"])
                (np_, ns, t2, a2, grads,
                 loss) = self._scaled_update(p, s, t, a, grads, sloss, lr,
                                             wd, lr_mult, wd_mult)
                if with_gnorm:
                    return (np_, ns, t2, a2), (loss, self._grad_norm(grads, s))
                return (np_, ns, t2, a2), loss

            carry, ys = jax.lax.scan(
                body, (params, opt_state, step_count, amp_state),
                (tuple(batches), keys, lrs))
            params, opt_state, t, amp_state = carry
            if with_gnorm:
                losses, gnorms = ys
                return params, opt_state, t, amp_state, losses, gnorms
            return params, opt_state, t, amp_state, ys

        fn = window_scaled if scaling else window_fn
        donate = (0, 1) if self.donate else ()
        if self.mesh is not None:
            opt_shardings = self._opt_shardings()
            wsharding = self.window_batch_sharding(accum)
            rep = NamedSharding(self.mesh, P())
            in_shardings = (
                self.param_sharding, opt_shardings, rep,
            ) + ((rep,) if scaling else ()) + (
                tuple(wsharding for _ in range(n_batch)),
                rep, rep, rep,
            )
            out_shardings = (self.param_sharding, opt_shardings, rep) \
                + ((rep,) if scaling else ()) + (rep,)
            if with_gnorm:
                out_shardings = out_shardings + (rep,)
            return jax.jit(fn, donate_argnums=donate,
                           in_shardings=in_shardings,
                           out_shardings=out_shardings)
        return jax.jit(fn, donate_argnums=donate)

    # -- public API ----------------------------------------------------------
    def __call__(self, *batch):
        """Run one step. batch = (x, label, ...) as NDArray/jax arrays.

        The host's side of the call is measured from inside, always: one
        :class:`~mxnet_tpu.observability.StepRecord` per call (phases
        ``mx.train.input`` / ``args`` / ``dispatch`` / ``after`` under
        ``mx.train.step``, each also a profiler annotation; docs/
        OBSERVABILITY.md "The step record"). Nothing on this path reads
        the device: with telemetry on, step n's loss is read
        ``TELEMETRY_LAG`` dispatches later."""
        obs_on = _obs.enabled()
        with _obs.step_record("train_step",
                              int(self.optimizer.num_update) + 1) as rec:
            with _obs.span("mx.train.input"):
                raws = tuple(b._data if isinstance(b, NDArray)
                             else jnp.asarray(b) for b in batch)
                if self.batch_sharding is not None and self._prefetcher is None:
                    # with a prefetcher attached the batch is already
                    # device-resident in the right sharding — re-placing it
                    # on the caller thread is exactly the hot-path tax the
                    # prefetcher exists to remove
                    raws = tuple(jax.device_put(r, self.batch_sharding)
                                 for r in raws)
            with _obs.span("mx.train.args"):
                # the resolved lr/wd multipliers fold into the compiled
                # program as constants, so the cache key carries them:
                # opt.set_lr_mult / param_dict edits after the first step
                # trigger a recompile instead of being silently frozen
                # (round-3 advisor finding)
                cache_key = self._step_cache_key(len(raws), obs_on)
                step = self._compiled.get(cache_key)
                missed = step is None
                if missed:
                    step = self._compiled[cache_key] = self._make_step(
                        len(raws), with_gnorm=obs_on)
                key = _rng.next_key()
                lr = jnp.float32(self.optimizer.learning_rate)
                wd = jnp.float32(self.optimizer.wd)
            loss = self._dispatch_recorded(rec, step, missed, cache_key,
                                           raws, key, lr, wd, obs_on)
        return loss

    def _dispatch_recorded(self, rec, fn, missed, cache_key, batch, keys, lr,
                           wd, obs_on, window=None, accum=1):
        """The part of a recorded call that the single step and the fused
        window share: the jitted call under ``mx.train.dispatch``; then,
        under ``mx.train.after``, the host's step count, the record's
        compile flag (a ``_compiled`` miss, or ``fn`` holding more programs
        than before the call), holding the telemetry, the capture hooks,
        monitors and the preemption check. Returns the loss(es), a future."""
        programs = _programs_held(fn)
        # measured profiling (docs/OBSERVABILITY.md): a periodic or
        # straggler-triggered capture traces THIS dispatch (a whole fused
        # window); one global read + call per step while disarmed.
        # Immediately before the guarded region — everything fallible after
        # begin must reach the abort handler, or a raise would leak the
        # trace session (it would disable every later capture in the process)
        ptok = _profiling.step_capture_begin(rec.step)
        try:
            with _obs.span("mx.train.dispatch"):
                loss, gnorm = self._dispatch(fn, batch, keys, lr, wd, obs_on)
        except BaseException:
            _profiling.step_capture_abort(ptok)
            raise
        with _obs.span("mx.train.after"):
            try:
                # host-side mirror (no device sync — loss is a future)
                self.optimizer.num_update += window or 1
                self._window_dispatches += window is not None
                rec.compiled = missed or _programs_held(fn) > programs
                if rec.compiled:
                    self._note_recompile(
                        cache_key, batch, kind="window" if window else "step")
                if obs_on:
                    self._hold(rec, batch, loss, gnorm, cache_key, window,
                               accum)
            except BaseException:
                _profiling.step_capture_abort(ptok)
                raise
            if ptok is not None:
                # the capture waits for this dispatch anyway: publish what
                # telemetry holds first, so that the parse/persist/retention
                # overhead of closing the traced window never counts into a
                # train_step_seconds observation
                self.flush_telemetry()
                _profiling.step_capture_end(ptok, loss)
            self._run_monitors()
            self._check_preemption()
        return loss

    def _dispatch(self, fn, batch, keys, lr, wd, with_gnorm):
        """Call the jitted step or window program on the carry and take the
        carry back: ``(loss or losses, gradient norm(s) or None)``. The
        outputs are futures; nothing here waits for the device."""
        carry = (self.params, self.opt_state, self.step_count)
        if self.amp_state is not None:
            carry += (self.amp_state,)
        out = fn(*carry, batch, keys, lr, wd)
        self.params, self.opt_state, self.step_count = out[:3]
        out = out[3:]
        if self.amp_state is not None:
            self.amp_state, out = out[0], out[1:]
        return out[0], (out[1] if with_gnorm else None)

    # -- fused multi-step window (docs/PERFORMANCE.md) -----------------------
    def attach_prefetcher(self, prefetcher):
        """Mark batches as arriving device-resident (sharded by an
        ``io.prefetch.DevicePrefetcher``): ``__call__``/``run`` skip the
        per-call ``jax.device_put``. Called by the prefetcher itself."""
        self._prefetcher = prefetcher
        return prefetcher

    def run(self, data_iter, steps=None, window=None, accum=None):
        """Run ``steps`` training steps in compiled windows of ``window``.

        Each full window lowers to ONE jitted XLA program — a
        ``jax.lax.scan`` of forward+backward+update over ``window`` stacked
        on-device batches with donated params/opt-state carry — so the
        fixed dispatch/readback cost is paid once per window instead of
        once per step. ``data_iter`` is any iterable of batches (tuples of
        arrays, ``DataBatch``, a ``DataLoader``), or an already-constructed
        :class:`~mxnet_tpu.io.prefetch.DevicePrefetcher` (e.g. from
        ``loader.prefetch_to_device(train_step, window)``); plain iterables
        are wrapped in a prefetcher so the sharded ``device_put`` + window
        stacking happen on a background thread, overlapped with compute.

        ``accum`` > 1 folds microbatch gradient accumulation into the same
        program: each step consumes ``accum`` batches from the iterator,
        accumulates grads in the fsdp storage layout, and applies the mean
        once. A trailing partial window falls back to single compiled
        steps (``accum == 1``) or a smaller window program (``accum > 1``,
        accumulation preserved; microbatches short of one full group are
        dropped and counted in ``prefetch_dropped_batches_total``).
        Monitor and preemption checks run at window boundaries.

        Returns the per-step losses as one stacked device future (shape
        ``[steps_run]``) — reading it is the only host sync.
        """
        import itertools

        from ..io.prefetch import DevicePrefetcher

        own = not isinstance(data_iter, DevicePrefetcher)
        if own:
            window = 8 if window is None else window
            accum = 1 if accum is None else accum
            # a DataLoader's __iter__ yields device-placed batches; sources
            # exposing the public host_batches() protocol (DataLoader, or
            # any custom loader opting in) feed the prefetcher their
            # host-side stream instead, so batches aren't placed, read
            # back, and placed again
            host_fn = getattr(data_iter, "host_batches", None)
            src = host_fn() if callable(host_fn) else data_iter
            if steps is not None:
                src = itertools.islice(iter(src), steps * accum)
            pf = DevicePrefetcher(src, train_step=self, window=window,
                                  accum=accum)
        else:
            pf = data_iter
            # the prefetcher already stacked its groups — a silently ignored
            # mismatching request would train at the wrong effective batch
            if window is not None and window != pf.window:
                raise ValueError(f"window={window} but the prefetcher was "
                                 f"built with window={pf.window}")
            if accum is not None and accum != pf.accum:
                raise ValueError(f"accum={accum} but the prefetcher was "
                                 f"built with accum={pf.accum}")
            window, accum = pf.window, pf.accum
            if steps is not None and steps % window:
                raise ValueError(
                    f"steps={steps} not divisible by the prefetcher's "
                    f"window={window}")
        losses = []
        done = 0
        try:
            while steps is None or done < steps:
                kind, payload, n = pf.next_group()
                if kind is None:
                    break
                if kind == "window":
                    losses.append(self._run_window(payload, n, accum))
                else:
                    losses.append(jnp.reshape(self(*payload), (1,)))
                done += n
        finally:
            if own:
                pf.close()
        if not losses:
            return jnp.zeros((0,), jnp.float32)
        return jnp.concatenate(losses) if len(losses) > 1 else losses[0]

    def _run_window(self, batches, window, accum):
        """Dispatch one compiled k-step window (batches already stacked +
        device-resident). One program, one dispatch, one step record
        (``loop="run_window"``), and no host sync: with telemetry on the
        window's losses are read ``TELEMETRY_LAG`` dispatches later."""
        obs_on = _obs.enabled()
        opt = self.optimizer
        with _obs.step_record("run_window",
                              int(opt.num_update) + window) as rec:
            with _obs.span("mx.train.args"):
                cache_key = self._window_cache_key(window, accum,
                                                   len(batches), obs_on)
                fn = self._compiled.get(cache_key)
                missed = fn is None
                if missed:
                    fn = self._compiled[cache_key] = self._make_window(
                        len(batches), window, accum, with_gnorm=obs_on)
                # draw the window's keys from the same host-side stream k
                # sequential __call__s would consume — the fused path is
                # bit-compatible with the single-step path for a fixed seed
                keys = jnp.stack([_rng.next_key() for _ in range(window)])
                # per-step lr vector: window step i reads the scheduler at
                # num_update + i, exactly what i sequential __call__s would
                # see
                if getattr(opt, "lr_scheduler", None) is not None:
                    base = opt.num_update
                    lrs = jnp.asarray([float(opt.lr_scheduler(base + i))
                                       for i in range(window)], jnp.float32)
                else:
                    lrs = jnp.full((window,), opt.learning_rate, jnp.float32)
                wd = jnp.float32(opt.wd)
            losses = self._dispatch_recorded(rec, fn, missed, cache_key,
                                             batches, keys, lrs, wd, obs_on,
                                             window, accum)
        return losses

    # -- telemetry (docs/OBSERVABILITY.md) -----------------------------------
    def _note_recompile(self, cache_key, raws, kind="step"):
        """Count a call that lowered or compiled a program (the step
        record's always-on ``compiled`` flag: a ``_compiled`` miss, or the
        jitted function holding more programs after the call than before)
        WITH its cause, telemetry on or off: jax.jit
        recompiles silently on any new (arity, shape, dtype,
        folded-constant) signature; under fusion that cost is invisible
        without this counter, and without the fingerprint diff the
        *reason* is guesswork. The guard diffs the new fingerprint against
        the closest seen program — the emitted ``recompile`` event carries
        ``cause`` + ``detail`` (e.g. ``arg0: [2, 3] -> [6, 3]``). Window-
        path misses (a new (window, accum, shapes) signature) keep their
        contractual ``reason="window"`` label."""
        from ..analysis import Fingerprint

        # the program key minus the telemetry flag: obs flipping on/off
        # changes the jit program (gnorm output) but not its identity
        fp = Fingerprint.of(raws, key=cache_key[:-1])
        reason = "window" if kind == "window" else None
        # group by program family: a step fingerprint diffed against a
        # window's stacked-batch fingerprint would report a phantom
        # shape change no input ever underwent
        label = self._recompile_guard.observe(fp, reason=reason, group=kind)
        if label is None:
            # lowered again though its shapes, dtypes and static arguments
            # were seen before: telemetry was switched (the gradient-norm
            # output is another program), or an argument changed its
            # placement (sharding, mesh) — the kind no fingerprint shows
            _obs.counter(self._recompile_guard.counter_name).inc(
                reason="other")
            _obs.emit("recompile", reason="other", cause="other",
                      detail="fingerprint seen before: telemetry switched, "
                      "or an argument's placement changed", **fp.describe())

    def model_flops_per_step(self, *batch, window: Optional[int] = None,
                             accum: int = 1) -> Optional[float]:
        """Analytic model FLOPs of one training step for this batch
        signature — the :func:`~mxnet_tpu.observability.goodput.
        program_flops` dot census of the lowered program (forward +
        backward dots; docs/OBSERVABILITY.md "Fleet view"). A fused
        window's scan body appears once in the program text, so the
        window census is one step (× ``accum`` microbatches). Returns
        None when the program holds no priceable dots."""
        if window:
            lower = lambda: self.lower_window_hlo(*batch, window=window,  # noqa: E731
                                                  accum=accum)
            key = self._window_cache_key(window, accum, len(batch),
                                         _obs.enabled())
        else:
            lower = lambda: self.lower_hlo(*batch)  # noqa: E731
            key = self._step_cache_key(len(batch), _obs.enabled())
        return self._estimate_flops(key, lower, accum)

    def _estimate_flops(self, cache_key, lower, accum=1):
        """Memoized dot-census FLOPs of one program; never raises — a
        telemetry estimate must not break the step loop."""
        if cache_key in self._flops_cache:
            return self._flops_cache[cache_key]
        flops = None
        try:
            from ..analysis import audit_lowered
            from ..observability.goodput import program_flops
            total = program_flops(audit_lowered(lower())).total * max(1, accum)
            flops = total or None
        except Exception:  # estimation is best-effort telemetry
            flops = None
        self._flops_cache[cache_key] = flops
        return flops

    def _record_flops(self, flops, step_seconds):
        """Export the FLOPs/step gauge and — against the ``peak_flops``
        config knob (``MXNET_TPU_PEAK_FLOPS``) — model FLOPs utilization."""
        if not flops:
            return
        from .. import config as _config

        _obs.gauge("train_model_flops_per_step",
                   "analytic model FLOPs per training step "
                   "(ProgramReport dot census)", unit="flops").set(flops)
        peak = float(_config.get("peak_flops"))
        if peak > 0 and step_seconds > 0:
            _obs.gauge("train_mfu",
                       "model FLOPs utilization vs the configured "
                       "peak_flops").set(flops / step_seconds / peak)

    def _amp_fetchable(self):
        """(scale, skipped) device scalars to ride the telemetry fetch, or
        None — so the amp gauges never cost a second host sync."""
        if self.amp_state is None:
            return None
        return (self.amp_state["scale"], self.amp_state["skipped"])

    # With telemetry on, a dispatch's readings (loss, gradient norm, loss
    # scale) are HELD as device futures and published TELEMETRY_LAG
    # dispatches later, or at obs.flush()/obs.shutdown(): reading them at
    # once would make the host wait for the step it has just queued, and the
    # device would then never have a second step to go on with.
    def _hold(self, rec, batch, loss, gnorm, cache_key, window=None, accum=1):
        """Hold one dispatch's readings: a step's (``batch`` its arrays), or
        a fused window's (``batch`` the stacked arrays)."""
        b0 = batch[0] if batch else None
        if window is None:
            samples = (int(b0.shape[0])
                       if b0 is not None and getattr(b0, "ndim", 0) else 1)
            lower = lambda: self.lower_hlo(*batch)  # noqa: E731
        else:
            nlead = 2 if accum > 1 else 1
            samples = (int(math.prod(b0.shape[:nlead + 1]))
                       if b0 is not None and b0.ndim > nlead else window)
            # the scan body appears once in the window program text, so its
            # census is one step's dots (one microbatch when accum > 1); the
            # per-step batch is sliced off the stack only on the memo miss
            lead = (0, 0) if accum > 1 else (0,)
            lower = lambda: self.lower_window_hlo(  # noqa: E731
                *(b[lead] for b in batch), window=window, accum=accum)
        _obs.set_step(rec.step)
        self._held.append(_Held(
            "train_step" if window is None else "run_window", rec.step,
            rec.t0 * 1e-9, (loss, gnorm, self._amp_fetchable()), window or 1,
            accum, samples, int(b0.size) if b0 is not None else 0,
            self._estimate_flops(cache_key, lower, accum)))
        if len(self._held) > TELEMETRY_LAG:
            self._publish(len(self._held) - TELEMETRY_LAG)

    def flush_telemetry(self):
        """Publish every held reading (blocks until those steps have run).
        ``obs.flush()`` and ``obs.shutdown()`` call it."""
        self._publish(len(self._held))

    def _publish(self, count):
        """Read the ``count`` oldest held dispatches (ONE ``device_get``,
        which waits for the newest of them only) and publish each under its
        own step number. ``train_step_seconds`` is the gap between
        successive completions as the host sees them: the time since the
        dispatch before was seen done (or since this one was submitted, if
        that was later), shared equally among dispatches seen done at one
        look. While work is queued that is the device's own step."""
        if count <= 0:
            return
        held = [self._held.popleft() for _ in range(count)]
        values = jax.device_get([h.values for h in held])
        now = time.perf_counter_ns() * 1e-9
        dt = (now - max(self._seen_done, held[0].t_entry)) / count
        self._seen_done = now
        for h, (loss_h, gnorm_h, amp_h) in zip(held, values):
            if h.loop == "run_window":
                last = {"loss": float(loss_h[-1]),
                        "grad_norm": None if gnorm_h is None
                        else float(gnorm_h[-1])}
            else:
                last = {"loss": float(loss_h),
                        "grad_norm": None if gnorm_h is None
                        else float(gnorm_h)}
            rate = h.tokens / dt if dt > 0 else 0.0
            _obs.histogram("train_step_seconds",
                           "gap between successive step completions as the "
                           "host sees them", unit="s").observe(dt, loop=h.loop)
            _obs.counter("train_steps_total").inc(h.window, loop=h.loop)
            _obs.counter("train_samples_total").inc(h.samples, loop=h.loop)
            _obs.counter("train_tokens_total").inc(h.tokens, loop=h.loop)
            _obs.gauge("train_tokens_per_sec", unit="tokens/s").set(rate)
            _obs.gauge("train_loss").set(last["loss"])
            if last["grad_norm"] is not None:
                _obs.gauge("train_grad_norm").set(last["grad_norm"])
            self._record_amp(amp_h)
            self._record_flops(h.flops, dt / h.window)
            common = dict(step=h.step, samples=h.samples, tokens=h.tokens,
                          tokens_per_sec=round(rate, 3), **last)
            if h.loop == "run_window":
                _obs.emit("train_window", window=h.window, accum=h.accum,
                          loss_mean=float(sum(float(x) for x in loss_h)
                                          / len(loss_h)),
                          window_seconds=round(dt, 6),
                          step_seconds_amortized=round(dt / h.window, 6),
                          **common)
            else:
                _obs.emit("train_step", step_seconds=round(dt, 6), **common)

    def _record_amp(self, amp_h):
        """Loss-scale gauge + skipped-step counter from the already-fetched
        ``(scale, skipped)`` host pair (float16 policy only) — part of the
        one telemetry read, never a second device_get."""
        if amp_h is None:
            return
        scale_f, skipped = amp_h
        _obs.gauge("train_loss_scale",
                   "current AMP dynamic loss scale").set(float(scale_f))
        d = int(skipped) - self._amp_skipped_seen
        if d > 0:
            _obs.counter("train_amp_skipped_steps_total",
                         "steps dropped by AMP overflow handling").inc(d)
        self._amp_skipped_seen = int(skipped)

    def attach_monitor(self, mon):
        """Register a :class:`~mxnet_tpu.monitor.Monitor`: at each step's
        interval boundary the compiled-side params are synced back into the
        Gluon block and the monitor's stat function observes them (grads
        live only inside the fused program and are summarized by the
        ``train_grad_norm`` gauge instead)."""
        mon._skip_grads = True  # Parameter grad buffers are stale here
        self._monitors.append(mon)
        return mon

    def _run_monitors(self):
        for m in self._monitors:
            m.tic()
            if m.activated:
                self.sync()
            m.toc_print()

    # -- graceful preemption (docs/RESILIENCE.md) ----------------------------
    def install_preemption(self, directory: str, guard=None,
                           exit_on_preempt: bool = True):
        """SIGTERM/SIGINT -> checkpoint into ``directory`` at the next step
        boundary, then raise :class:`~mxnet_tpu.resilience.Preempted` (a
        ``SystemExit(0)``) so the process exits cleanly. Returns the
        installed guard (``guard.request()`` triggers the same path without
        a real signal; ``exit_on_preempt=False`` checkpoints but lets the
        caller's loop observe ``guard.requested`` and wind down itself)."""
        from ..resilience import PreemptionGuard

        self._preempt_guard = (guard or PreemptionGuard()).install()
        self._preempt_dir = directory
        self._preempt_exit = exit_on_preempt
        self._preempt_saved = False  # re-arm the one-shot save on reinstall
        return self._preempt_guard

    def _check_preemption(self):
        g = self._preempt_guard
        if g is None or not g.requested:
            return
        from ..resilience import Preempted

        # one-shot: with exit_on_preempt=False the caller's loop may drain
        # more steps before winding down — don't re-save a full checkpoint
        # at every one of them
        if not getattr(self, "_preempt_saved", False):
            self.save(self._preempt_dir)
            self._preempt_saved = True
        if self._preempt_exit:
            raise Preempted(g.signum)

    # -- amp policy introspection (docs/PERFORMANCE.md) ----------------------
    @property
    def loss_scale(self):
        """Current dynamic loss scale (host float; syncs). None unless the
        policy is float16."""
        if self.amp_state is None:
            return None
        return float(jax.device_get(self.amp_state["scale"]))

    @property
    def amp_skipped_steps(self):
        """Total steps dropped by in-graph overflow handling (host int;
        syncs). 0 unless the policy is float16."""
        if self.amp_state is None:
            return 0
        return int(jax.device_get(self.amp_state["skipped"]))

    def sync(self):
        """Write compiled-side params back into the Gluon block."""
        for p in self._plist:
            p._nd._data = self.params[p.name]

    # -- checkpoint / resume (SURVEY §5.4 recovery story) --------------------
    def save(self, directory):
        from ..checkpoint import save_train_state

        self.flush_telemetry()  # the save waits for the device anyway
        # the checkpoint step is num_update (ATTEMPTED steps, the schedule
        # clock); the meta extras carry what differs from it under the f16
        # policy: the APPLIED count (Adam's t, held back on skips) and the
        # dynamic-loss-scale carry — without them a preemption restart
        # would inflate t and reset the scale to its 2^16 init
        extra = {"applied_step": int(jax.device_get(self.step_count))}
        if self.amp_state is not None:
            a = jax.device_get(self.amp_state)
            extra["amp_state"] = {"scale": float(a["scale"]),
                                  "good": int(a["good"]),
                                  "skipped": int(a["skipped"])}
        return save_train_state(directory, int(self.optimizer.num_update),
                                self.params, self.opt_state, extra=extra,
                                layout=self.layout.to_dict()
                                if self.layout is not None else None)

    def restore(self, directory):
        import json
        import os

        from ..checkpoint import (checkpoint_layout, latest_checkpoint,
                                  load_train_state)

        path = latest_checkpoint(directory)
        if path is None:
            return False
        # declared-vs-restored layout validation: the manifest records the
        # Layout that wrote the checkpoint; model axes (tp/sp/pp/ep) and
        # rules must match the current spec — resharding across those is
        # not a data relayout but a different program. Data axes (dp/fsdp)
        # are free: that IS the elastic contract.
        recorded = checkpoint_layout(path)
        if recorded is not None and self.layout is not None:
            why = self.layout.compatible_restore(recorded)
            if why is not None:
                raise ValueError(
                    f"checkpoint {path} layout incompatible with the "
                    f"current layout: {why}")
        params, opt_state, step = load_train_state(
            path, like=(self.params, self.opt_state))
        import jax.numpy as jnp

        meta = {}
        try:
            with open(os.path.join(path, "meta.json")) as f:
                meta = json.load(f)
        except (OSError, ValueError):
            pass  # pre-extra checkpoints: fall back to step for everything
        self.params = {k: jnp.asarray(v) for k, v in params.items()}
        self.opt_state = jax.tree_util.tree_map(jnp.asarray, opt_state)
        self.step_count = self._on_mesh(jnp.asarray(
            int(meta.get("applied_step", step)), jnp.int32))
        self.optimizer.num_update = step
        if self.amp_state is not None and "amp_state" in meta:
            a = meta["amp_state"]
            self.amp_state = self._on_mesh(
                {"scale": jnp.float32(a["scale"]),
                 "good": jnp.int32(a["good"]),
                 "skipped": jnp.int32(a["skipped"])})
            self._amp_skipped_seen = int(a["skipped"])
        if self.param_sharding is not None:
            # reshard-on-restore (docs/RESILIENCE.md "Elastic training"):
            # the checkpoint reassembled to host-global arrays whatever
            # world wrote it; lay params AND optimizer state back out onto
            # the CURRENT mesh — after an elastic scale-down/up this is
            # where the fsdp layout changes width
            from .sharding import reshard_tree

            if self.layout is not None and self.layout.total > 1:
                # one source of truth: the declarative Layout derives the
                # storage shardings, same spec the manifest recorded
                self.params = reshard_tree(
                    self.params, layout=self.layout, mesh=self.mesh)
                self.opt_state = reshard_tree(
                    self.opt_state, layout=self.layout, mesh=self.mesh)
            else:
                self.params = reshard_tree(self.params, self.param_sharding)
                self.opt_state = reshard_tree(self.opt_state,
                                              self.param_sharding)
        self.sync()
        return True

    def lower_hlo(self, *batch):
        """Lower (don't run) the SAME program ``__call__`` would execute
        for this batch signature: the resolved lr/wd multipliers, the mesh
        in/out shardings, the telemetry-mode grad-norm output, and the jit
        cache are all shared — so HLO assertions inspect the real
        executable, and a later ``__call__`` with the same signature reuses
        this jit function instead of compiling a second program."""
        obs_on = _obs.enabled()
        raws = tuple(b._data if isinstance(b, NDArray) else jnp.asarray(b) for b in batch)
        if self.batch_sharding is not None and self._prefetcher is None:
            raws = tuple(jax.device_put(r, self.batch_sharding) for r in raws)
        cache_key = self._step_cache_key(len(raws), obs_on)
        step = self._compiled.get(cache_key)
        if step is None:
            step = self._compiled[cache_key] = self._make_step(
                len(raws), with_gnorm=obs_on)
        # a CONSTANT dummy key: lower() never executes the program, only
        # shape/dtype matter — drawing from the live stream would make an
        # audit()/lower_hlo() call mid-run perturb every later step's
        # dropout, breaking fixed-seed reproducibility
        key = jax.random.key(0)
        lr = jnp.float32(self.optimizer.learning_rate)
        wd = jnp.float32(self.optimizer.wd)
        if self.amp_state is not None:
            return step.lower(self.params, self.opt_state, self.step_count,
                              self.amp_state, raws, key, lr, wd)
        return step.lower(self.params, self.opt_state, self.step_count, raws,
                          key, lr, wd)

    def lower_window_hlo(self, *batch, window: int = 2, accum: int = 1):
        """Lower (don't run) the fused k-step window program ``run()``
        would execute for this per-step batch signature — the batch is
        tiled to the stacked ``[window, (accum,) ...]`` layout and the
        window jit cache is shared, exactly like :meth:`lower_hlo` shares
        the step cache."""
        obs_on = _obs.enabled()
        raws = tuple(b._data if isinstance(b, NDArray) else jnp.asarray(b)
                     for b in batch)
        lead = (window,) if accum == 1 else (window, accum)
        stacked = tuple(jnp.broadcast_to(r, lead + r.shape) for r in raws)
        if self.batch_sharding is not None:
            ws = self.window_batch_sharding(accum)
            stacked = tuple(jax.device_put(s, ws) for s in stacked)
        cache_key = self._window_cache_key(window, accum, len(raws), obs_on)
        fn = self._compiled.get(cache_key)
        if fn is None:
            fn = self._compiled[cache_key] = self._make_window(
                len(raws), window, accum, with_gnorm=obs_on)
        # constant dummy keys, same reason as lower_hlo: lowering must not
        # consume the live training key stream
        keys = jax.random.split(jax.random.key(0), window)
        lrs = jnp.full((window,), self.optimizer.learning_rate, jnp.float32)
        wd = jnp.float32(self.optimizer.wd)
        if self.amp_state is not None:
            return fn.lower(self.params, self.opt_state, self.step_count,
                            self.amp_state, stacked, keys, lrs, wd)
        return fn.lower(self.params, self.opt_state, self.step_count,
                        stacked, keys, lrs, wd)

    def op_scopes(self, *batch, window: Optional[int] = None,
                  accum: int = 1) -> Dict[str, str]:
        """{HLO instruction name: scope path} of the compiled program this
        batch signature runs: the join key for a device trace, whose rows
        carry an instruction's text and not its ``op_name``
        (``MeasuredReport.scope_seconds`` takes it; docs/OBSERVABILITY.md
        "Named scopes"). Paths start at one of ``SCOPES`` or ``backward``.

        Compiles the lowered program once more, apart from the one that
        runs: jax leaves metadata out of its compile cache's key, so the
        running executable may have been cached before the scopes existed,
        and its text then names none of them (its instruction names are the
        same). An explicit compiler option, set to its default, keeps this
        compile from being handed that executable, and the scopes are made
        part of the key."""
        from ..observability.scopes import op_scopes_from_hlo, scoped_text

        lowered = (self.lower_window_hlo(*batch, window=window, accum=accum)
                   if window else self.lower_hlo(*batch))
        return op_scopes_from_hlo(scoped_text(lowered))

    def audit(self, *batch, window: Optional[int] = None, accum: int = 1,
              compile: bool = True, rules: Optional[ShardingRules] = None):
        """Structural :class:`~mxnet_tpu.analysis.ProgramAudit` of the
        program this batch signature runs (docs/ANALYSIS.md): the lowered
        StableHLO report (dtype census — assert bf16 dots / no f64 leaks
        here), the compiled HLO report (collectives, donation aliases),
        and the flat input indices of the donated params/opt-state carry
        so ``audit(...).carry_donation() == 1.0`` is the whole no-copy
        update check. ``window=`` audits the fused k-step scan program
        instead of the single step.

        On a mesh the audit also carries the sharding-and-communication
        layer: ``audit.contract`` diffs the declared parameter layouts
        (``rules=`` overrides the step's own rules as the declaration
        under check) against the layouts the program actually compiled —
        every mismatch rendered as ``name: declared P('fsdp', None) →
        compiled replicated`` — and ``audit.comm`` prices every
        collective into a :class:`~mxnet_tpu.analysis.CommReport`
        (per-axis logical bytes, accidental-reshard flags; the intended
        ZeRO compute gathers are exempt).

        ``audit.memory`` is the buffer-liveness residency estimate
        (:class:`~mxnet_tpu.analysis.MemoryReport`): peak bytes with the
        donated carry counted once, a residency timeline, and category
        attribution — ``params`` / ``opt_state`` leaves of the carry,
        ``batch`` for the data inputs, everything the program
        materializes under ``activations`` (``make memcheck`` gates
        these per program family)."""
        from .. import analysis as _analysis

        if window:
            lowered = self.lower_window_hlo(*batch, window=window,
                                            accum=accum)
        else:
            lowered = self.lower_hlo(*batch)
        # flat arg order is tree_flatten order: params dict leaves first,
        # then opt-state leaves — exactly the donated (0, 1) argnums
        n_params = len(jax.tree_util.tree_leaves(self.params))
        n_carry = len(jax.tree_util.tree_leaves((self.params,
                                                 self.opt_state)))
        lowered_rep = _analysis.audit_lowered(lowered)
        compiled_rep = (_analysis.audit_compiled(lowered.compile())
                        if compile else None)
        # memory truth follows the same precedence as donation: the
        # compiled executable (scheduled, fused) when available
        mem_rep = compiled_rep if compiled_rep is not None else lowered_rep
        mem_cats = {i: ("params" if i < n_params else "opt_state")
                    for i in range(n_carry)}
        # past the carry: step count, optional amp carry, then the batch
        # arrays, key and scalar hyperparams — everything array-shaped
        # there is batch data, the scalars are noise either way
        for i in range(n_carry, len(mem_rep.inputs)):
            mem_cats[i] = "batch"
        memory = _analysis.memory_report(mem_rep, categories=mem_cats)
        contract: list = []
        comm = None
        if self.mesh is not None:
            # layout truth: the compiled executable when available, else
            # the lowered annotations (same precedence as carry_donation)
            rep = compiled_rep if compiled_rep is not None else lowered_rep
            decl_rules = rules if rules is not None else self.rules
            shapes = {k: tuple(v.shape) for k, v in self.params.items()}
            declared = decl_rules.declared_tree_specs(shapes, self.mesh)
            # flat input order of a dict pytree is sorted-key order, so
            # param i of the donated carry is the i-th sorted name
            order = {name: i for i, name in enumerate(sorted(shapes))}
            contract = _analysis.check_contract(rep, declared, shapes,
                                                order, self.mesh)
            comm = _analysis.comm_report(rep, self.mesh)
            comm.reshards = _analysis.detect_accidental_reshards(
                rep, declared, shapes, intended=set(self._compute_specs),
                mesh=self.mesh)
        else:
            # mesh-less: no layouts to contract-check, but any collective
            # that crept into a single-device program is still priced
            comm = _analysis.comm_report(
                compiled_rep if compiled_rep is not None else lowered_rep)
        return _analysis.ProgramAudit(
            lowered=lowered_rep, compiled=compiled_rep,
            carry_indices=tuple(range(n_carry)),
            contract=contract, comm=comm, memory=memory)

    def profile(self, *batch, steps: int = 2, warmup: int = 1,
                window: Optional[int] = None, accum: int = 1,
                trace_dir: Optional[str] = None):
        """Trace ``steps`` REAL training steps of this batch signature
        (after ``warmup`` untraced ones) and return the
        :class:`~mxnet_tpu.observability.profiling.Capture` — measured
        per-device op timeline, hot-op ranking, measured step time and
        compute/collective overlap (docs/OBSERVABILITY.md "Measured
        profiling"). The dispatch goes through ``__call__``/``run``'s own
        jit cache, so the traced program IS the production program — and
        the profiled steps advance the training state exactly like any
        other steps. ``window=`` profiles the fused k-step scan program
        instead of the single step (one traced dispatch per window)."""
        if window:
            raws = tuple(b._data if isinstance(b, NDArray)
                         else jnp.asarray(b) for b in batch)
            lead = (window,) if accum == 1 else (window, accum)
            stacked = tuple(jnp.broadcast_to(r, lead + r.shape)
                            for r in raws)
            if self.batch_sharding is not None:
                ws = self.window_batch_sharding(accum)
                stacked = tuple(jax.device_put(s, ws) for s in stacked)
            dispatch = lambda: self._run_window(stacked, window, accum)  # noqa: E731
        else:
            dispatch = lambda: self(*batch)  # noqa: E731

        def fn():
            # capture() waits for every traced dispatch anyway: publish its
            # telemetry there and then, so train_step_seconds holds one
            # whole step for each of them, as the trace does
            out = jax.block_until_ready(dispatch())
            self.flush_telemetry()
            return out

        return _profiling.capture(fn, steps=steps, warmup=warmup,
                                  trace_dir=trace_dir)
