"""Sharding policy: parameter PartitionSpecs, activation hints, and the
`Runtime` object threaded through the model code.

Mesh convention (launch/mesh.py):
  single-pod : (data=16, model=16)          axes ("data", "model")
  multi-pod  : (pod=2, data=16, model=16)   axes ("pod", "data", "model")

Roles:
  - "model": tensor parallelism (attention heads / FFN columns / vocab) and
    the intra-expert TP axis for MoE.
  - "data": batch data-parallelism + FSDP weight sharding + the
    expert-parallel axis for MoE.
  - "pod": pure data parallelism across pods (weights replicated across
    pods; gradient all-reduce crosses the inter-pod links only once per
    step).
"""

from __future__ import annotations

import dataclasses
import functools
import re
from concurrent.futures import ThreadPoolExecutor
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from ..launch.mesh import Mesh, make_mesh, shard_map_compat


# ===========================================================================
# Segment sharding for the relation engine (DESIGN.md §9)


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Contiguous segment shards over the ``("data",)`` device mesh.

    Shard ``k`` owns segments ``[bounds[k], bounds[k+1])`` and produces +
    retains exactly those blocks on ``devices[k]``.  Contiguity matters:
    Morton-ordered segments make each shard a spatially compact region, so
    cross-shard completion traffic concentrates on shard-boundary faces
    (the partition-owned-storage idiom of data-parallel unstructured
    rendering).  ``devices`` may repeat (more shards than devices — the
    plan is then purely logical and no arrays are committed)."""

    n_segments: int
    bounds: Tuple[int, ...]          # len n_shards + 1; [0] == 0, [-1] == ns
    devices: Tuple[Any, ...]         # one device per shard (None = default)

    @property
    def n_shards(self) -> int:
        return len(self.bounds) - 1

    @property
    def multi_device(self) -> bool:
        """True when every shard sits on its own distinct device (the
        collective-exchange path is only meaningful then)."""
        devs = [d for d in self.devices if d is not None]
        return (len(devs) == self.n_shards > 1
                and len({d.id for d in devs}) == self.n_shards)

    def shard_of(self, segment: int) -> int:
        return int(np.searchsorted(np.asarray(self.bounds[1:]),
                                   int(segment), side="right"))

    def shard_of_array(self, segments) -> np.ndarray:
        return np.searchsorted(np.asarray(self.bounds[1:]),
                               np.asarray(segments), side="right")

    def shard_bounds(self, shard: int) -> Tuple[int, int]:
        return self.bounds[shard], self.bounds[shard + 1]

    def rehomed(self, lost: int, target: int) -> "ShardPlan":
        """The plan after shard ``lost``'s device died and its segments
        were re-homed onto shard ``target``'s device (DESIGN.md §12).
        Segment ownership (``bounds``) is unchanged — only the lost slot's
        device is replaced, so every placement lookup ``devices[k]`` keeps
        working; the plan then has duplicate devices, like the purely
        logical more-shards-than-devices case."""
        devices = list(self.devices)
        devices[lost] = devices[target]
        return ShardPlan(self.n_segments, self.bounds, tuple(devices))

    def segments(self, shard: int) -> range:
        return range(self.bounds[shard], self.bounds[shard + 1])

    @staticmethod
    def make(n_segments: int, shards: int = 1,
             devices: Optional[Sequence[Any]] = None) -> "ShardPlan":
        """Even contiguous split of ``n_segments`` into ``shards`` shards,
        devices round-robin over ``jax.devices()`` (shards=1 stays off the
        device API entirely: the unsharded engine must not force backend
        initialisation or placement)."""
        n_segments = int(n_segments)
        shards = max(1, min(int(shards), max(1, n_segments)))
        base, rem = divmod(n_segments, shards)
        bounds = [0]
        for k in range(shards):
            bounds.append(bounds[-1] + base + (1 if k < rem else 0))
        if devices is None:
            if shards == 1:
                devices = (None,)
            else:
                devs = jax.devices()
                devices = tuple(devs[k % len(devs)] for k in range(shards))
        return ShardPlan(n_segments, tuple(bounds), tuple(devices))


def make_data_mesh(n_shards: int):
    """The ``("data",)`` mesh for the sharded relation engine — built via
    the launch/mesh.py shims only."""
    return make_mesh((int(n_shards),), ("data",))


def compile_on_devices(fn, args: tuple, devices: Sequence[Any]) -> None:
    """Compile the jitted ``fn`` for ``fn(*args)`` on each of ``devices``,
    all at once on threads.

    JAX keys an executable by its device, so a kernel that K shards run on
    K devices compiles K times, and under the engine lock those compiles
    run one after another. Run together they take about as long as one.
    The later ``fn(*args)`` on any of these devices reuses its executable
    (same trace, same lowering, same device). Array leaves of ``args`` give
    only their shape and dtype; every other argument is passed as is."""
    def one(device):
        sh = SingleDeviceSharding(device)
        lowered = fn.lower(*[
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
            if isinstance(a, jax.Array) else a for a in args])
        lowered.compile()

    with ThreadPoolExecutor(max_workers=len(devices)) as pool:
        list(pool.map(one, devices))


def _psum_pair(c, l):
    return jax.lax.psum(c, "data"), jax.lax.psum(l, "data")


@functools.lru_cache(maxsize=None)
def _psum_exchange(devices: tuple):
    """The ``("data",)`` mesh over ``devices`` and ONE jitted psum over it,
    built once per device tuple, so repeated exchanges of one shape reuse
    one executable instead of tracing and compiling a new one each call."""
    mesh = make_mesh((len(devices),), ("data",), devices=list(devices))
    spec = P("data")
    return mesh, jax.jit(shard_map_compat(
        _psum_pair, mesh=mesh, in_specs=(spec, spec), out_specs=(P(), P())))


def all_sum_shards(parts: List[Tuple[Any, Any]],
                   devices: Optional[Sequence[Any]] = None):
    """Integer sum of per-shard ``(cand, cand_len)`` contributions.

    Each completion pair has exactly one owning shard; the owner contributes
    the gathered pool rows, every other shard exact zeros, so an elementwise
    integer sum reconstructs the single-pool candidate matrix bit-for-bit
    (DESIGN.md §9).  With one distinct device per part the sum runs as a
    ``psum`` over a ``("data",)`` mesh of exactly those devices, so every
    part stays on the device that computed it; otherwise (shards
    sharing a device, e.g. tier-1 on one CPU device) it stacks and sums on
    the first part's device — identical integers either way.

    Returns ``(cand, cand_len, exchange)`` with ``exchange`` one of
    ``"none"`` (a single part), ``"psum"`` or ``"stack"``.
    """
    if len(parts) == 1:
        return parts[0][0], parts[0][1], "none"
    cands = [p[0] for p in parts]
    lens = [p[1] for p in parts]
    n = len(parts)
    dev_ids = ({d.id for d in devices if d is not None}
               if devices is not None else set())
    if devices is not None and len(dev_ids) == n:
        mesh, psum = _psum_exchange(tuple(devices))
        spec = P("data")
        c_parts = [jax.device_put(c[None], d)
                   for c, d in zip(cands, devices)]
        l_parts = [jax.device_put(l[None], d)
                   for l, d in zip(lens, devices)]
        gc = jax.make_array_from_single_device_arrays(
            (n,) + cands[0].shape, NamedSharding(mesh, spec), c_parts)
        gl = jax.make_array_from_single_device_arrays(
            (n,) + lens[0].shape, NamedSharding(mesh, spec), l_parts)
        sc, sl = psum(gc, gl)
        return sc[0], sl[0], "psum"
    tgt = None
    if devices is not None:
        for d in devices:
            if d is not None:
                tgt = d
                break
    if tgt is not None:
        cands = [jax.device_put(c, tgt) for c in cands]
        lens = [jax.device_put(l, tgt) for l in lens]
    return (jnp.sum(jnp.stack(cands), axis=0),
            jnp.sum(jnp.stack(lens), axis=0), "stack")


@dataclasses.dataclass
class Runtime:
    """Execution context handed to model code. With mesh=None everything is
    a no-op (single-device smoke tests)."""

    mesh: Optional[Mesh] = None
    # segment-shard assignment for the relation engine (DESIGN.md §9);
    # None = unsharded
    shard_plan: Optional[ShardPlan] = None
    batch_axes: Tuple[str, ...] = ("data",)
    fsdp_axis: Optional[str] = "data"
    tp_axis: Optional[str] = "model"
    remat: str = "full"            # none | dots | full
    moe_impl: str = "shard_map"    # shard_map | local
    seq_shard_decode: bool = False  # shard long KV caches over fsdp axis
    # -- perf knobs (EXPERIMENTS.md §Perf) ----------------------------------
    seq_parallel: bool = False      # Megatron-SP: shard stored activations'
    #                                 sequence dim over the TP axis
    bf16_gather: bool = False       # cast fp32 masters to bf16 BEFORE the
    #                                 FSDP all-gather (halves weight traffic)
    moe_ep: str = "data"            # EP axis: "data" (a2a dispatch) or
    #                                 "model" (replicated-activation EP:
    #                                 zero-ICI dispatch + one psum combine)
    loss_chunk: int = 0             # chunked cross-entropy: scan the vocab
    #                                 projection over sequence chunks so the
    #                                 f32 logits never materialize fully

    @property
    def ep_size(self) -> int:
        if self.mesh is None or self.moe_impl != "shard_map":
            return 1
        ax = self.fsdp_axis if self.moe_ep == "data" else self.tp_axis
        return self.mesh.shape[ax]

    # -- activation hints ----------------------------------------------------

    def _hint(self, x, spec):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def hint_act(self, x):
        """(B, S, D) hidden states: batch-sharded. With seq_parallel the
        sequence dim is additionally sharded over the TP axis between blocks
        (Megatron-SP): remat-saved residuals shrink by the TP degree; GSPMD
        inserts the gather/scatter around attention."""
        if self.mesh is None:
            return x
        spec = [self.batch_axes] + [None] * (x.ndim - 1)
        if (self.seq_parallel and x.ndim >= 3 and x.shape[1] > 1
                and x.shape[1] % self.mesh.shape[self.tp_axis] == 0):
            spec[1] = self.tp_axis
        return self._hint(x, P(*spec))

    def hint_logits(self, x):
        """(B, S, V): vocab sharded over the TP axis."""
        if self.mesh is None:
            return x
        return self._hint(x, P(self.batch_axes, None, self.tp_axis))

    def hint_heads(self, x):
        """(B, S, H, hd) attention activations: heads on the TP axis
        (GSPMD pads uneven head counts)."""
        if self.mesh is None:
            return x
        return self._hint(x, P(self.batch_axes, None, self.tp_axis, None))

    def hint_kv_seq(self, x):
        """(B, T, kv, hd) decode KV cache: keep the sequence axis sharded
        over the TP axis through the attention math (flash-decode). Without
        this pin, GSPMD's propagation re-gathers the full cache per layer.
        Long contexts (batch=1) give the fsdp axis to the sequence instead
        of the batch."""
        if self.mesh is None:
            return x
        if self.seq_shard_decode:
            return self._hint(x, P(None, (self.fsdp_axis, self.tp_axis),
                                   None, None))
        return self._hint(x, P(self.batch_axes, self.tp_axis, None, None))

    # -- flash-decode attention ----------------------------------------------

    def flash_decode(self, q, K, V, pos):
        """Distributed decode attention over a sequence-sharded KV cache
        (shard_map: GSPMD's propagation otherwise re-gathers the cache).

        q (B,1,H,hd), K/V (B,T,kv,hd) seq-sharded over the TP axis (+fsdp
        for long contexts), pos (B,). Two-pass online softmax: local max ->
        pmax, local exp-sums and weighted values -> psum, divide. Exact."""
        if self.mesh is None:
            return None
        B, T = K.shape[0], K.shape[1]
        t = self.tp_axis
        s_names = ((self.fsdp_axis, t) if self.seq_shard_decode else (t,))
        s_size = int(np.prod([self.mesh.shape[n] for n in s_names]))
        if T % s_size != 0:
            return None
        nb = int(np.prod([self.mesh.shape[n] for n in self.batch_axes]))
        bspec = self.batch_axes if B % nb == 0 else None
        s_ax = s_names if len(s_names) > 1 else s_names[0]
        H = q.shape[2]

        def body(q_, K_, V_, pos_):
            rep = H // K_.shape[2]
            kf = jnp.repeat(K_, rep, axis=2) if rep > 1 else K_
            vf = jnp.repeat(V_, rep, axis=2) if rep > 1 else V_
            t_loc = K_.shape[1]
            off = jnp.zeros((), jnp.int32)
            mult = t_loc
            for name in reversed(s_names):
                off = off + jax.lax.axis_index(name) * mult
                mult = mult * self.mesh.shape[name]
            iota = off + jnp.arange(t_loc)
            mask = (iota[None, :] <= pos_[:, None])[:, None, None, :]
            s = jnp.einsum("bshd,bthd->bhst", q_, kf.astype(q_.dtype),
                           preferred_element_type=jnp.float32)
            s = s / np.sqrt(q_.shape[-1])
            s = jnp.where(mask, s, -jnp.inf)
            m_loc = s.max(axis=-1)                       # (B,H,1)
            m = jax.lax.pmax(m_loc, s_ax)
            e = jnp.exp(s - m[..., None])
            e = jnp.where(mask, e, 0.0)
            den = jax.lax.psum(e.sum(axis=-1), s_ax)     # (B,H,1)
            num = jnp.einsum("bhst,bthd->bshd", e.astype(q_.dtype),
                             vf.astype(q_.dtype))
            num = jax.lax.psum(num, s_ax)
            out = num / jnp.maximum(
                jnp.swapaxes(den, 1, 2)[..., None], 1e-30).astype(q_.dtype)
            return out.astype(q_.dtype)

        return shard_map_compat(
            body, mesh=self.mesh,
            in_specs=(P(bspec, None, None, None),
                      P(bspec, s_ax, None, None),
                      P(bspec, s_ax, None, None), P(bspec)),
            out_specs=P(bspec, None, None, None),
        )(q, K, V, pos)

    # -- MoE dispatch ----------------------------------------------------------

    def moe_param_specs(self):
        if self.moe_ep == "model":
            # experts sharded over the TP axis, full ff per expert
            e = self.tp_axis
            return {"router": P(None, None), "wi": P(e, None, None),
                    "wg": P(e, None, None), "wo": P(e, None, None)}
        return {"router": P(None, None),
                "wi": P(self.fsdp_axis, None, self.tp_axis),
                "wg": P(self.fsdp_axis, None, self.tp_axis),
                "wo": P(self.fsdp_axis, self.tp_axis, None)}

    def moe_apply(self, p, x_flat, cfg, dtype):
        from ..models.moe import moe_ffn, moe_ffn_ep_replicated
        if self.mesh is None or self.moe_impl != "shard_map":
            return moe_ffn(p, x_flat, cfg, dtype)
        tok_spec = P(self.batch_axes, None)
        if self.moe_ep == "model":
            # tokens are TP-replicated between blocks; each model row picks
            # the pairs routed to ITS experts locally (no a2a) and the
            # outputs combine with a single psum.
            fn = shard_map_compat(
                lambda pp, xx: moe_ffn_ep_replicated(
                    pp, xx, cfg, dtype, ep_axis=self.tp_axis),
                mesh=self.mesh,
                in_specs=(self.moe_param_specs(), tok_spec),
                out_specs=tok_spec,
            )
            return fn(p, x_flat)
        fn = shard_map_compat(
            lambda pp, xx: moe_ffn(pp, xx, cfg, dtype,
                                   ep_axis=self.fsdp_axis,
                                   tp_axis=self.tp_axis),
            mesh=self.mesh,
            in_specs=(self.moe_param_specs(), tok_spec),
            out_specs=tok_spec,
        )
        return fn(p, x_flat)


# ===========================================================================
# Parameter sharding rules


_RULES = [
    # (path regex, spec builder (f=fsdp axis, t=tp axis))
    # vocab-only embedding sharding: sharding D over the data axis makes the
    # (B,S,D) embedding output's D fight the batch axis and GSPMD emits
    # full-batch seq-sharded reshard buffers (§Perf iteration A4)
    (r"embed/table$",        lambda f, t: P(t, None)),
    (r"unembed/w$",          lambda f, t: P(None, t)),
    # head-shaped attention projections: TP on the head axis
    (r"(attn|xattn)/wq$",    lambda f, t: P(f, t, None)),
    (r"(attn|xattn)/w[kv]$", lambda f, t: P(f, None, None)),
    (r"(attn|xattn)/wo$",    lambda f, t: P(t, None, f)),
    (r"(attn|xattn)/bq$",    lambda f, t: P(t, None)),
    (r"(attn|xattn)/b[kv]$", lambda f, t: P()),
    (r"mlp/w[ig]/w$",        lambda f, t: P(f, t)),
    (r"mlp/wg/w$",           lambda f, t: P(f, t)),
    (r"mlp/wo/w$",           lambda f, t: P(t, f)),
    (r"moe/router$",         lambda f, t: P(None, None)),
    (r"moe/w[ig]$",          lambda f, t: P(f, None, t)),
    (r"moe/wo$",             lambda f, t: P(f, t, None)),
    (r"mix/in_proj/w$",      lambda f, t: P(f, t)),
    (r"mix/out_proj/w$",     lambda f, t: P(t, f)),
    (r"mix/conv_[wb]$",      lambda f, t: P()),
    (r"mix/(A_log|D|dt_bias)$", lambda f, t: P()),
    (r"mix/norm/g$",         lambda f, t: P()),
    (r"shared_attn/in_proj/w$", lambda f, t: P(f, None)),
    (r"pos_(enc|dec)$",      lambda f, t: P(None, f)),
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
    return "/".join(parts)


def param_spec(path, leaf, fsdp: str, tp: str) -> P:
    ps = _path_str(path)
    base = None
    for pat, builder in _RULES:
        if re.search(pat, ps):
            base = builder(fsdp, tp)
            break
    if base is None:
        base = P()  # norms, biases, scalars: replicate
    # stacked layer dims (scan) prepend None axes
    extra = leaf.ndim - len(base)
    if extra < 0:
        base = P(*tuple(base)[-leaf.ndim:]) if leaf.ndim else P()
        extra = leaf.ndim - len(base)
    spec = P(*(([None] * extra) + list(base)))
    # drop axes that do not divide the dim (e.g. tiny smoke shapes)
    return spec


def make_param_shardings(mesh: Mesh, params_shape, fsdp="data", tp="model",
                         moe_ep="data"):
    """NamedShardings for a params pytree (or its eval_shape).

    fsdp=None -> weight-stationary (serving): parameters are sharded over
    the TP axis only, so decode never re-gathers weights."""
    def fix(path, leaf):
        ps = _path_str(path)
        if moe_ep == "model" and re.search(r"moe/w[igo]$", ps):
            spec = P(*([None] * (leaf.ndim - 3) + [tp, None, None]))
        else:
            spec = param_spec(path, leaf, fsdp, tp)
        # validate divisibility; drop offending axes
        axes = list(spec)
        for i, ax in enumerate(axes):
            if ax is None:
                continue
            names = ax if isinstance(ax, tuple) else (ax,)
            size = int(np.prod([mesh.shape[n] for n in names]))
            if leaf.shape[i] % size != 0:
                axes[i] = None
        return NamedSharding(mesh, P(*axes))
    return jax.tree_util.tree_map_with_path(fix, params_shape)


def batch_specs(shape_kind: str, cfg, rt: Runtime):
    """PartitionSpecs for the input batch of each step kind."""
    b = rt.batch_axes
    if cfg.family == "encdec":
        if shape_kind == "train":
            return {"frames": P(b, None, None), "tokens": P(b, None),
                    "labels": P(b, None)}
        if shape_kind == "prefill":
            return {"frames": P(b, None, None), "tokens": P(b, None)}
        return {"token": P(b, None), "pos": P(b)}
    specs = {}
    if shape_kind == "train":
        specs = {"tokens": P(b, None), "labels": P(b, None)}
    elif shape_kind == "prefill":
        specs = {"tokens": P(b, None)}
    else:
        specs = {"token": P(b, None), "pos": P(b)}
    if cfg.family == "vlm":
        if shape_kind in ("train", "prefill"):
            specs["vision_embeds"] = P(b, None, None)
            specs["positions3d"] = P(None, b, None)
        else:
            specs["positions3d"] = P(None, b, None)
    return specs


def cache_specs(cfg, rt: Runtime, long_context: bool = False):
    """PartitionSpecs for decode caches (see lm.init_cache layouts).

    KV caches are sharded along the **sequence** axis over the TP mesh axis
    (flash-decode): per-chip score blocks stay local and the distributed
    softmax costs only tiny max/sum all-reduces, instead of GSPMD
    re-gathering the whole cache per layer (§Perf cell C). Long contexts
    additionally shard the sequence over the fsdp axis."""
    b = rt.batch_axes
    t = rt.tp_axis
    s_ax = (rt.fsdp_axis, t) if long_context else t
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        kv = P(None, b, s_ax, None, None)   # (L, B, S, kv_heads, hd)
        return (kv, kv)
    if fam == "ssm":
        return (P(None, b, None, t, None), P(None, b, None, t))
    if fam == "hybrid":
        m = (P(None, None, b, None, t, None), P(None, None, b, None, t))
        kv = P(None, b, s_ax, None, None)
        return (m, (kv, kv))
    if fam == "encdec":
        kv = P(None, b, s_ax, None, None)
        return ((kv, kv), P(b, None, None))
    raise ValueError(fam)


def normalize_shardings(mesh: Mesh, specs, shapes):
    """Turn a pytree of PartitionSpecs into NamedShardings, dropping axes
    that do not divide the corresponding dim (e.g. batch=1 long-context)."""
    def fix(spec, leaf):
        axes = list(spec) + [None] * (len(leaf.shape) - len(spec))
        for i, ax in enumerate(axes):
            if ax is None:
                continue
            names = ax if isinstance(ax, tuple) else (ax,)
            size = int(np.prod([mesh.shape[n] for n in names]))
            if leaf.shape[i] % size != 0:
                axes[i] = None
        return NamedSharding(mesh, P(*axes))
    return jax.tree.map(fix, specs, shapes,
                        is_leaf=lambda x: isinstance(x, P))
