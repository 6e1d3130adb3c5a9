"""Block / HybridBlock (reference: ``python/mxnet/gluon/block.py``).

``HybridBlock.hybridize()`` is the reference's bridge from imperative code to
the compiled world (trace → nnvm graph → ``CachedOp`` with static memory
planning, ``src/imperative/cached_op.cc``). The TPU design stages the same
trace into ``jax.jit`` instead:

  - first call runs eagerly (triggers deferred parameter init, like the
    reference's shape-inference-on-first-forward);
  - subsequent calls hit a jitted pure function keyed on (input shapes,
    dtypes, train-mode) — the jit cache is the analog of CachedOp's
    per-signature graph cache and of bucketing;
  - parameters enter as traced arguments (not baked constants), so one
    compiled program serves every optimizer step;
  - stochastic layers draw from a per-call PRNG key argument
    (``random.trace_key_scope``), keeping eager and hybrid runs reproducible;
  - in-trace state writes (BatchNorm running stats) are collected on a state
    tape and returned as extra outputs, then written back concretely —
    replacing the reference's mutable aux-state kernels functionally.

Eager-vs-hybridized equivalence is the core test invariant (SURVEY §4).
"""
from __future__ import annotations

import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp

from .. import autograd as _ag
from .. import ndarray as nd
from .. import random as _rng
from ..base import MXNetError
from ..ndarray import NDArray
from .parameter import DeferredInitializationError, Parameter, ParameterDict

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


class _BlockScope:
    """Naming scope: generates unique prefixes like the reference."""

    _tls = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._tls, "current", None)
        if current is None:
            if prefix is None:
                prefix = _global_count(hint)
            return prefix, ParameterDict(prefix, shared=params)
        if prefix is None:
            count = current._counter.get(hint, 0)
            current._counter[hint] = count + 1
            prefix = f"{hint}{count}_"
        full = current._block.prefix + prefix
        shared = params if params is not None else current._block._params._shared
        return full, ParameterDict(full, shared=shared)

    def __enter__(self):
        self._old = getattr(_BlockScope._tls, "current", None)
        _BlockScope._tls.current = self
        return self

    def __exit__(self, *exc):
        _BlockScope._tls.current = self._old


_GLOBAL_COUNT = {}
_NAME_LOCK = threading.Lock()

# global-policy epoch folded into every jit-cache signature: bumped when a
# process-wide compile-affecting policy flips (e.g. amp.init), so programs
# traced under the old policy are not replayed under the new one
_CACHE_EPOCH = [0]
_EPOCH_LOCK = threading.Lock()


def bump_global_cache_epoch():
    # amp.init/_reset may flip the policy from a worker thread while other
    # threads read the epoch into jit-cache keys (JH005)
    with _EPOCH_LOCK:
        _CACHE_EPOCH[0] += 1


def _global_count(hint):
    # blocks may be constructed from loader/serving threads (JH005)
    with _NAME_LOCK:
        n = _GLOBAL_COUNT.get(hint, 0)
        _GLOBAL_COUNT[hint] = n + 1
    return f"{hint}{n}_"


# state tape for in-trace parameter writes (BatchNorm moving stats)
class _TraceState(threading.local):
    def __init__(self):
        self.active = False
        self.updates = []  # list[(Parameter, raw)]
        self.force_eager = False  # deferred-init pass: children must not jit
        self.symbolic = False  # export pass: hybrid_forward sees the sym namespace
        self.block_prefix = ""  # prefix of the block whose forward is being staged


_TRACE = _TraceState()

_DUMMY_KEY = None


def _dummy_key():
    """Fixed key for traced programs that never draw randomness."""
    global _DUMMY_KEY
    if _DUMMY_KEY is None:
        _DUMMY_KEY = jax.random.key(0)
    return _DUMMY_KEY


def record_state_update(param, new_raw):
    """Layers call this instead of assigning ``param.data()._data`` directly."""
    if _TRACE.active:
        _TRACE.updates.append((param, new_raw))
    else:
        param._nd._data = jax.lax.stop_gradient(
            new_raw._data if isinstance(new_raw, NDArray) else new_raw)


def _flatten_nds(out):
    """Flatten nested (tuple/list) NDArray outputs -> (raw_list, rebuild_fn)."""
    raws = []

    def walk(o):
        if isinstance(o, NDArray):
            raws.append(o._data)
            return ("nd", len(raws) - 1)
        if isinstance(o, (tuple, list)):
            return (type(o).__name__, [walk(x) for x in o])
        return ("const", o)

    spec = walk(out)

    def rebuild(new_raws, spec=spec):
        def un(s):
            kind = s[0]
            if kind == "nd":
                v = new_raws[s[1]]
                return v if isinstance(v, NDArray) else NDArray(v)
            if kind in ("tuple", "list"):
                seq = [un(x) for x in s[1]]
                return tuple(seq) if kind == "tuple" else seq
            return s[1]

        return un(spec)

    return raws, rebuild


def _resolve_remat_policy(remat):
    """Normalize a ``hybridize(remat=...)`` value to a jax.checkpoint policy.

    ``True``/``'full'`` → save nothing (recompute everything in backward);
    a string names a ``jax.checkpoint_policies`` member (``'dots_saveable'``,
    ``'nothing_saveable'``, ``'dots_with_no_batch_dims_saveable'``, ...);
    a callable passes through as a custom policy.
    """
    if remat is True or remat == "full":
        return None  # jax.checkpoint default: save nothing
    if callable(remat):
        return remat
    if isinstance(remat, str):
        pol = getattr(jax.checkpoint_policies, remat, None)
        if pol is None:
            avail = [n for n in dir(jax.checkpoint_policies)
                     if not n.startswith("_")]
            raise ValueError(f"unknown remat policy {remat!r}; available: "
                             f"'full', {avail}")
        return pol
    raise ValueError(f"remat= must be True, 'full', a jax.checkpoint_policies "
                     f"name, or a callable policy, got {remat!r}")


class Block:
    """Base container: parameter registration + eager forward."""

    # classes that form a rematerialization unit under ``hybridize(remat=)``
    # (one jax.checkpoint per instance): the transformer/GPT-2/BERT layer
    # stacks set this True so long-context training trades flops for peak
    # activation memory deliberately (docs/PERFORMANCE.md "Mixed precision")
    _remat_unit = False

    def __init__(self, prefix=None, params=None):
        self._empty_init_done = True
        self._prefix, self._params = _BlockScope.create(prefix, params, self._alias())
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = OrderedDict()
        self._forward_hooks = []
        self._forward_pre_hooks = []
        self._remat = None

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._prefix.rstrip("_")

    @property
    def params(self):
        return self._params

    def name_scope(self):
        return self._scope

    # -- attribute-based registration ---------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            existing = self.__dict__.get("_reg_params")
            if existing is not None:
                existing[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)

    # -- parameter management -----------------------------------------------
    def collect_params(self, select=None):
        import re

        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self._params)
        else:
            pat = re.compile(select)
            ret.update({k: v for k, v in self._params.items() if pat.match(k)})
        for child in self._children.values():
            ret.update(child.collect_params(select))
        # tied parameters (params= sharing) appear under each sharer's local
        # name — keep the first occurrence only, so Trainer/optimizer see one
        # entry (no double state, no double allreduce contribution)
        seen = set()
        for k in list(ret.keys()):
            pid = id(ret[k])
            if pid in seen:
                ret.pop(k)
            else:
                seen.add(pid)
        return ret

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        self.collect_params().initialize(init=init, ctx=ctx, force_reinit=force_reinit)
        return self

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for p in self._params.values():
            p.cast(dtype)
        return self

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # -- structural (prefix-independent) serialization -----------------------
    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + n: p for n, p in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename, deduplicate=False):
        from ..serialization import save_ndarrays

        params = self._collect_params_with_prefix()
        save_ndarrays(filename, {k: p.data() for k, p in params.items() if p._nd is not None})

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False, dtype_source="current"):
        from ..serialization import load_ndarrays

        loaded = load_ndarrays(filename)
        params = self._collect_params_with_prefix()
        for name, p in params.items():
            if name in loaded:
                p.set_data(loaded[name])
            elif not allow_missing:
                raise MXNetError(f"Parameter {name} missing in {filename}")
        if not ignore_extra:
            extra = set(loaded) - set(params)
            if extra:
                raise MXNetError(f"{filename} contains unknown parameters {sorted(extra)[:5]}")

    # pytorch-style aliases used by some reference-era scripts
    save_params = save_parameters

    def load_params(self, filename, ctx=None, **kw):
        self.load_parameters(filename, ctx=ctx, **kw)

    # -- call ---------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        if _TRACE.active and not _TRACE.symbolic:
            # staged (TrainStep's loss or a hybridized jit): name the device
            # operations of this block after it, so a trace tells attention
            # from feed-forward from the MLM head with no model edited
            # (docs/OBSERVABILITY.md "Named scopes"). Metadata only.
            outer = _TRACE.block_prefix
            _TRACE.block_prefix = self._prefix
            try:
                with jax.named_scope(self._scope_label(outer)):
                    out = self.forward(*args, **kwargs)
            finally:
                _TRACE.block_prefix = outer
        else:
            out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def _scope_label(self, outer):
        """This block's name for a trace scope: its own name less the
        prefix of the block it is called from (``attn`` inside
        ``..._layer3_``, not the whole chain again); a container that
        shares its parent's prefix goes by its class."""
        name = self.name
        if outer and self._prefix == outer:
            return self._alias()
        if outer and name.startswith(outer) and len(name) > len(outer):
            return name[len(outer):]
        return name or self._alias()

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def hybridize(self, active=True, **kwargs):
        # remat threads recursively: every block stores the policy, but only
        # ``_remat_unit`` classes actually wrap their forward in
        # jax.checkpoint (one unit per layer, no nesting in the model zoos).
        # remat=False clears; remat=None (absent) leaves the setting alone.
        r = kwargs.get("remat", None)
        if r is not None:
            self._remat = None if r is False else r
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def summary(self, *inputs):
        out = self(*inputs)
        nparams = sum(p.data().size for p in self.collect_params().values() if p._nd is not None)
        print(f"{self.__class__.__name__}: {nparams} parameters")
        return out

    def __repr__(self):
        lines = [f"{self.__class__.__name__}("]
        for name, child in self._children.items():
            body = repr(child).replace("\n", "\n  ")
            lines.append(f"  ({name}): {body}")
        lines.append(")")
        return "\n".join(lines)


class _HybridTrace:
    """Context: swap params to tracers, bind RNG + train-mode, collect state."""

    def __init__(self, params, raws, train, key):
        self.params = params
        self.raws = raws
        self.train = train
        self.key = key

    def __enter__(self):
        self._saved = [p._nd._data for p in self.params]
        for p, r in zip(self.params, self.raws):
            p._nd._data = r
        self._ag_scope = _ag._RecordScope(False, self.train)
        self._ag_scope.__enter__()
        self._key_scope = _rng.trace_key_scope(self.key)
        self._key_scope.__enter__()
        self._trace_was = (_TRACE.active, _TRACE.updates)
        _TRACE.active, _TRACE.updates = True, []
        return self

    def __exit__(self, *exc):
        self.state_updates = _TRACE.updates
        _TRACE.active, _TRACE.updates = self._trace_was
        self._key_scope.__exit__(*exc)
        self.rng_uses = self._key_scope.uses
        self._ag_scope.__exit__(*exc)
        for p, s in zip(self.params, self._saved):
            p._nd._data = s


class HybridBlock(Block):
    """Block whose forward can be staged into one XLA computation."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._jit_cache = {}
        self._static_alloc = False

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  inline_limit=2, forward_bulk_size=None, backward_bulk_size=None,
                  remat=None):
        """``remat=`` installs an activation-rematerialization policy on this
        block and its children: ``True``/``'full'`` (recompute everything),
        a ``jax.checkpoint_policies`` name such as ``'dots_saveable'``, or a
        callable; ``False`` clears it. Applied as ``jax.checkpoint`` around
        each ``_remat_unit`` layer when the forward is staged (TrainStep or
        a hybridized jit) — set it BEFORE building a TrainStep, whose
        program cache does not watch this flag."""
        self._active = active
        self._static_alloc = static_alloc  # maps to buffer donation (future)
        if remat is not None:
            if remat is not False:
                _resolve_remat_policy(remat)  # validate eagerly
            self._remat = None if remat is False else remat
        self._jit_cache.clear()
        super().hybridize(active, remat=remat)

    def infer_shape(self, *args):
        """Hook for deferred-init shape inference; layers override."""
        raise DeferredInitializationError(
            f"{self.__class__.__name__} has deferred-initialized parameters and "
            "no infer_shape; run one eager forward or initialize with full shapes")

    # -- hybrid_forward plumbing --------------------------------------------
    def forward(self, x, *args, **kwargs):
        if _TRACE.symbolic:
            from .. import symbol as sym_mod

            params = {name: p.var() for name, p in self._reg_params.items()}
            return self.hybrid_forward(sym_mod, x, *args, **params, **kwargs)
        params = {}
        try:
            for name, p in self._reg_params.items():
                params[name] = p.data()
        except DeferredInitializationError:
            self._deferred_infer(x, *args)
            params = {name: p.data() for name, p in self._reg_params.items()}
        return self.hybrid_forward(nd, x, *args, **params, **kwargs)

    def _deferred_infer(self, *args):
        self.infer_shape(*args)
        for p in self._reg_params.values():
            if p._deferred_init is not None:
                p._finish_deferred_init(p.shape)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # -- staged call --------------------------------------------------------
    def __call__(self, *args, **kwargs):
        if (self._remat is not None and type(self)._remat_unit
                and _TRACE.active and not _TRACE.force_eager
                and not _TRACE.symbolic):
            # inside a staged trace (TrainStep loss or a hybridized jit):
            # wrap this layer in jax.checkpoint so its activations are
            # recomputed, not saved, during backward
            return self._call_remat(args, kwargs)
        if (not self._active or _TRACE.active or _TRACE.force_eager
                or _TRACE.symbolic or kwargs):
            return super().__call__(*args, **kwargs)
        return self._call_cached(*args)

    def _call_remat(self, args, kwargs):
        """Run this block's forward under ``jax.checkpoint`` with the
        installed policy. Parameters and NDArray arguments enter as explicit
        checkpoint inputs (differentiation-correct); non-array arguments
        (None masks, python flags) ride the closure. Blocks that record
        state updates (BatchNorm) must not be remat units — the state tape
        would leak tracers out of the checkpointed trace."""
        policy = _resolve_remat_policy(self._remat)
        plist = [p for _, p in sorted(self.collect_params().items())]
        if any(p._nd is None for p in plist):
            return Block.__call__(self, *args, **kwargs)  # deferred init
        param_raws = tuple(p._nd._data for p in plist)
        nd_idx = [i for i, a in enumerate(args) if isinstance(a, NDArray)]
        arg_raws = tuple(args[i]._data for i in nd_idx)
        cell = {}

        def fn(praws, araws):
            saved = [p._nd._data for p in plist]
            for p, r in zip(plist, praws):
                p._nd._data = r
            try:
                call_args = list(args)
                for i, r in zip(nd_idx, araws):
                    call_args[i] = NDArray(r)
                out = Block.__call__(self, *call_args, **kwargs)
            finally:
                for p, s in zip(plist, saved):
                    p._nd._data = s
            raws, rebuild = _flatten_nds(out)
            cell["rebuild"] = rebuild
            return tuple(raws)

        out_raws = jax.checkpoint(fn, policy=policy)(param_raws, arg_raws)
        return cell["rebuild"]([NDArray(r) for r in out_raws])

    def _call_cached(self, *args):
        plist = [p for _, p in sorted(self.collect_params().items())]
        if any(p._nd is None for p in plist):
            # first call runs eagerly to trigger deferred init (reference
            # semantics: shape inference happens on first forward). Children
            # must not stage their own jits during this pass — it would
            # fragment compilation and consume PRNG keys out of order.
            _TRACE.force_eager = True
            try:
                return super().__call__(*args)
            finally:
                _TRACE.force_eager = False
        return self._run_jit(plist, args)

    def _run_jit(self, plist, args):
        arg_raws = [a._data if isinstance(a, NDArray) else a for a in args]
        train = _ag.is_training()
        sig = (train, _CACHE_EPOCH[0], tuple(
            (tuple(r.shape), str(r.dtype)) if hasattr(r, "shape") else ("py", repr(r))
            for r in arg_raws))
        entry = self._jit_cache.get(sig)
        if entry is None:
            entry = self._build_jit(plist, args, train)
            self._jit_cache[sig] = entry
        jfn, rebuild_cell, nstate_cell = entry
        # only consume global RNG state if the traced program draws from it —
        # keeps eager and hybridized key chains aligned for deterministic nets
        key = _rng.next_key() if nstate_cell.get("uses_rng", False) else _dummy_key()
        param_raws = tuple(p._nd._data for p in plist)
        out_raws, state_raws = jfn(param_raws, tuple(arg_raws), key)
        for (p, _), s in zip(nstate_cell["state_params"], state_raws):
            p._nd._data = s
        rebuild = rebuild_cell["rebuild"]
        if _ag.is_recording():
            node_inputs = [p._nd for p in plist] + [a for a in args if isinstance(a, NDArray)]
            nd_positions = [i for i, a in enumerate(args) if isinstance(a, NDArray)]
            const_args = list(arg_raws)

            def replay_op(*flat, _np=len(plist), _key=key, _consts=const_args,
                          _pos=nd_positions, _jfn=jfn):
                pr = tuple(flat[:_np])
                ar = list(_consts)
                for p_i, v in zip(_pos, flat[_np:]):
                    ar[p_i] = v
                outs, _states = _jfn(pr, tuple(ar), _key)
                return tuple(outs)

            node = _ag.TapeNode(replay_op, {}, node_inputs, len(out_raws), self.name)
            wrapped = []
            for i, r in enumerate(out_raws):
                w = NDArray(r)
                w._tape = (node, i)
                wrapped.append(w)
            return rebuild(wrapped)
        return rebuild(list(out_raws))

    def _build_jit(self, plist, args, train):
        rebuild_cell = {"rebuild": None}
        nstate_cell = {"state_params": []}
        arg_is_nd = [isinstance(a, NDArray) for a in args]

        def pure(param_raws, arg_raws, key):
            with _HybridTrace(plist, param_raws, train, key) as tr:
                call_args = [NDArray(r) if is_nd else r
                             for r, is_nd in zip(arg_raws, arg_is_nd)]
                out = Block.__call__(self, *call_args)
                raws, rebuild = _flatten_nds(out)
            rebuild_cell["rebuild"] = rebuild
            nstate_cell["state_params"] = [(p, None) for p, _ in tr.state_updates]
            nstate_cell["uses_rng"] = tr.rng_uses > 0
            states = tuple(jax.lax.stop_gradient(s) for _, s in tr.state_updates)
            return tuple(raws), states

        return jax.jit(pure), rebuild_cell, nstate_cell

    # -- deployment (reference: HybridBlock.export -> symbol.json + params) --
    def trace_symbol(self, *input_names):
        """Trace this block's forward into a Symbol graph (parameters become
        named variables). The reference got the same artifact from the
        CachedOp's nnvm graph."""
        from .. import symbol as sym_mod

        input_names = input_names or ("data",)
        saved = _TRACE.symbolic
        _TRACE.symbolic = True
        try:
            out = Block.__call__(self, *[sym_mod.var(n) for n in input_names])
        finally:
            _TRACE.symbolic = saved
        return out

    def export(self, path, epoch=0, input_names=("data",)):
        """Write ``path-symbol.json`` + ``path-{epoch}.params`` (reference
        deploy format: arg:-prefixed names)."""
        from ..serialization import save_ndarrays

        out = self.trace_symbol(*input_names)
        if isinstance(out, (tuple, list)):
            from .. import symbol as sym_mod

            out = sym_mod.Group(list(out))
        out.save(f"{path}-symbol.json")
        fname = f"{path}-{epoch:04d}.params"
        by_name = {p.name: p for p in self.collect_params().values()
                   if p._nd is not None}
        save_ndarrays(fname, {("arg:" + k): p.data() for k, p in by_name.items()})
        return f"{path}-symbol.json", fname


class SymbolBlock(Block):
    """Runs an exported symbol.json graph (reference: deploy path —
    ``SymbolBlock.imports(sym, ['data'], params_file)``)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="symbolblock_", params=None)
        from .. import symbol as sym_mod

        self._out_symbol = outputs
        self._input_names = [i.name if isinstance(i, sym_mod.Symbol) else i
                             for i in (inputs if isinstance(inputs, (list, tuple))
                                       else [inputs])]
        arg_names = outputs.list_arguments()
        for name in arg_names:
            if name in self._input_names:
                continue
            p = Parameter(name, allow_deferred_init=True)
            self._params._params[name] = p
            if params and name in params:
                p.set_data(params[name])

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as sym_mod
        from ..serialization import load_ndarrays

        out = sym_mod.load(symbol_file)
        params = {}
        if param_file:
            loaded = load_ndarrays(param_file)
            params = {k.removeprefix("arg:").removeprefix("aux:"): v
                      for k, v in loaded.items()}
        if isinstance(input_names, str):
            input_names = [input_names]
        return SymbolBlock(out, input_names, params)

    def forward(self, *args):
        from .. import symbol as sym_mod

        env = dict(zip(self._input_names, args))
        for name, p in self._params.items():
            if p._nd is not None:
                env[name] = p.data()
        return sym_mod.eval_symbol(self._out_symbol, env)
