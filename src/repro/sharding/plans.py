"""Parallelism plans: logical-axis -> mesh-axis rule tables.

A plan is two rule dicts (params vs activations — the same logical name
can shard differently: weight "embed" dims shard over `data` for
FSDP/ZeRO-3 while activation "embed" stays unsharded) plus the batch
axes.  Rule values may be a single mesh axis or a tuple (e.g. batch over
("pod", "data")).

Plans:
  dp        pure data parallel (params replicated)
  fsdp      ZeRO-3 params over `data`, activations DP only
  tp        tensor parallel over `model`, DP over `data`
  fsdp_tp   2D: ZeRO-3 over `data` x TP over `model`   (default)
  fsdp_tp_sp  + sequence-parallel long-context decode (KV over `data`)

Serving has its own plan shape (``serving_plan`` / ``ServingPlan``
below): a 1-axis tensor-parallel mesh over which the transformer
weights shard head-wise / column-row-wise and the KV cache shards along
the KV-head dimension, while every scheduler-owned operand stays
replicated.  See the ServingPlan docstring for the full mesh/axis
contract.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple, Union

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]

# logical axes that carry the TP dimension of weights/activations
_TP_PARAM = ("heads", "kv_heads", "mlp", "vocab", "experts", "ssm_inner",
             "ssm_heads")


@dataclasses.dataclass(frozen=True)
class Plan:
    name: str
    param_rules: Dict[str, Axis]
    act_rules: Dict[str, Axis]
    batch_axes: Axis                    # mesh axes carrying the batch dim
    kv_seq_axis: Axis = None            # SP: decode KV sequence sharding

    def with_pod(self) -> "Plan":
        """Extend for the multi-pod mesh: `pod` joins the batch axes."""
        batch = self.batch_axes
        if batch is None:
            batch = ("pod",)
        elif isinstance(batch, str):
            batch = ("pod", batch)
        else:
            batch = ("pod",) + tuple(batch)
        return dataclasses.replace(self, batch_axes=batch)


def _plan_dp() -> Plan:
    return Plan("dp", param_rules={}, act_rules={"batch": "data", "tokens": "data"},
                batch_axes="data")


def _plan_fsdp() -> Plan:
    return Plan(
        "fsdp",
        param_rules={"embed": "data", "vocab": "data", "mlp": "data",
                     "ssm_inner": "data"},
        act_rules={"batch": "data", "tokens": "data"},
        batch_axes="data",
    )


def _plan_tp() -> Plan:
    pr = {ax: "model" for ax in _TP_PARAM}
    ar = {"batch": "data", "tokens": "data", "heads": "model",
          "kv_heads": "model", "mlp": "model", "experts": "model",
          "vocab": "model", "ssm_inner": "model", "ssm_heads": "model"}
    return Plan("tp", param_rules=pr, act_rules=ar, batch_axes="data")


def _plan_fsdp_tp() -> Plan:
    pr = {ax: "model" for ax in _TP_PARAM}
    pr["embed"] = "data"                 # ZeRO-3 on the non-TP dim
    ar = {"batch": "data", "tokens": "data", "heads": "model",
          "kv_heads": "model", "mlp": "model", "experts": "model",
          "vocab": "model", "ssm_inner": "model", "ssm_heads": "model"}
    return Plan("fsdp_tp", param_rules=pr, act_rules=ar, batch_axes="data")


def _plan_fsdp_tp_sp() -> Plan:
    base = _plan_fsdp_tp()
    return dataclasses.replace(base, name="fsdp_tp_sp", kv_seq_axis="data")


def _plan_fsdp_tp_spact() -> Plan:
    """fsdp_tp + Megatron-style activation sequence sharding: the
    residual stream ("seq") shards over `model` between blocks, so
    remat-saved activations shrink by the TP degree; block-internal
    tensors keep TP sharding (their constraints don't name "seq")."""
    base = _plan_fsdp_tp()
    ar = dict(base.act_rules)
    ar["seq"] = "model"
    return dataclasses.replace(base, name="fsdp_tp_spact", act_rules=ar)


_PLANS = {p.name: p for p in (_plan_dp(), _plan_fsdp(), _plan_tp(),
                              _plan_fsdp_tp(), _plan_fsdp_tp_sp(),
                              _plan_fsdp_tp_spact())}


def get_plan(name: str, *, multi_pod: bool = False) -> Plan:
    plan = _PLANS[name]
    return plan.with_pod() if multi_pod else plan


def default_plan(cfg, shape, *, multi_pod: bool = False) -> Plan:
    """Pick the baseline plan for an (arch, shape) cell.

    Long-context decode at tiny batch can't DP-shard; it needs the KV
    sequence spread over `data` (flash-decode split-K) -> SP plan.
    """
    if shape.kind == "decode" and shape.global_batch < 16:
        return get_plan("fsdp_tp_sp", multi_pod=multi_pod)
    return get_plan("fsdp_tp", multi_pod=multi_pod)


# ----------------------------------------------------------------------
# serving: tensor-parallel plan over a 1-axis device mesh
# ----------------------------------------------------------------------

SERVING_TP_AXIS = "tp"


@dataclasses.dataclass(frozen=True)
class ServingPlan:
    """Tensor-parallel serving contract over a 1-axis ``tp`` mesh.

    Mesh/axis contract (runtime/server.py, launch/mesh.make_tp_mesh):

      * The mesh has exactly one axis (default name ``"tp"``) of size
        ``tp`` — the tensor-parallel degree.  Serving never shards the
        slot/batch axis: block tables, the refcounted allocator and the
        radix prefix tree are host-side numpy structures replicated in
        meaning across devices, so paging, prefix sharing and
        speculative decoding compose with TP unchanged.
      * Weights shard Megatron-style through ``param_rules``: qkv and
        the MLP up/gate projections column-parallel (logical axes
        ``heads`` / ``kv_heads`` / ``mlp`` carry ``tp``), the attention
        out-projection and MLP down-projection row-parallel (their
        leading ``heads`` / ``mlp`` dim carries ``tp``), and the
        embedding/unembedding over ``vocab``.  Logical dims that do not
        divide the mesh fall back to replicated
        (models/common.partition_specs).
      * The KV cache — the paged pool ``[L, num_blocks, KH,
        block_size, hd]`` (fp8 scales ``[L, num_blocks, KH,
        block_size]``) — shards its KV-head dim over ``tp``; each device holds every pool block but only ``KH / tp``
        heads of it, so the per-device KV bytes shrink by the TP degree
        while the host allocator keeps addressing whole logical blocks.
        Requires ``KH % tp == 0`` (the server asserts).
      * Every other jit operand (tokens, positions, block tables,
        output buffer, n-gram table) is replicated: ``replicated``.
      * Activations inside the jitted steps follow ``act_rules``
        (heads/kv_heads/mlp/vocab over ``tp``; batch/seq/embed
        replicated), applied via sharding.axes.use_rules at trace time.

    Cross-shard float reductions (attention out-projection, MLP
    down-projection) are single dots accumulating in fp32
    (models/{attention,mlp}.py); the psum of their shard partials runs
    in fp32, so ``tp>1`` agrees with ``tp=1`` to the order of those
    sums, within a bf16 tolerance (see runtime/server.py).
    """

    mesh: Mesh
    axis: str = SERVING_TP_AXIS

    @property
    def tp(self) -> int:
        return self.mesh.shape[self.axis]

    @property
    def param_rules(self) -> Dict[str, Axis]:
        return {ax: self.axis for ax in _TP_PARAM}

    @property
    def act_rules(self) -> Dict[str, Axis]:
        return {"heads": self.axis, "kv_heads": self.axis,
                "mlp": self.axis, "vocab": self.axis,
                "experts": self.axis}

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def param_shardings(self, cfg):
        """NamedSharding pytree for the full parameter tree of `cfg`
        (non-divisible dims replicate, mirroring partition_specs)."""
        import jax
        from repro.models import api
        mesh_sizes = {self.axis: self.tp}
        pspecs = api.pspecs(cfg, self.param_rules, mesh_sizes)
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), pspecs,
            is_leaf=lambda x: isinstance(x, P))

    def cache_sharding(self, cfg) -> NamedSharding:
        """Paged KV pool sharding over the KV-head dim: index 2 of every
        leaf ([L, num_blocks, KH, block_size, ...]).  Falls back to
        replicated when KH doesn't divide."""
        ax = self.axis if (cfg.num_kv_heads
                           and cfg.num_kv_heads % self.tp == 0) else None
        return NamedSharding(self.mesh, P(None, None, ax))


def serving_plan(mesh: Mesh, axis: str = SERVING_TP_AXIS) -> ServingPlan:
    """The tensor-parallel serving plan for a 1-axis mesh (see
    ServingPlan for the full mesh/axis contract)."""
    assert axis in mesh.axis_names, (axis, mesh.axis_names)
    return ServingPlan(mesh=mesh, axis=axis)


# ----------------------------------------------------------------------
# input / cache partition specs for a cell
# ----------------------------------------------------------------------

def batch_pspec(plan: Plan, batch_size: int, mesh_shape: Dict[str, int],
                extra_dims: int = 0) -> P:
    """Sharding for [B, ...] inputs; replicates when B is too small."""
    axes = plan.batch_axes
    if axes is None:
        return P(*([None] * (1 + extra_dims)))
    ax_tuple = (axes,) if isinstance(axes, str) else tuple(axes)
    size = 1
    for a in ax_tuple:
        size *= mesh_shape[a]
    if batch_size % size:
        # fall back to the largest prefix of the axes that divides B
        keep = []
        size = 1
        for a in ax_tuple:
            if batch_size % (size * mesh_shape[a]) == 0:
                keep.append(a)
                size *= mesh_shape[a]
        ax_tuple = tuple(keep)
    spec = tuple(ax_tuple) if ax_tuple else None
    return P(spec, *([None] * extra_dims))


def input_pspecs(cfg, shape, plan: Plan, mesh) -> Dict[str, P]:
    """PartitionSpec per input tensor of a cell (matches api.input_specs)."""
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    from repro.models import api
    specs = api.input_specs(cfg, shape)
    out = {}
    for name, sds in specs.items():
        nd = len(sds.shape)
        if nd == 0:
            out[name] = P()
        else:
            out[name] = batch_pspec(plan, sds.shape[0], mesh_shape,
                                    extra_dims=nd - 1)
    return out


def cache_pspecs(cfg, shape, plan: Plan, mesh):
    """PartitionSpecs for the decode cache pytree.

    KV tensors [L, B, T, KH, hd]: batch over the plan's batch axes when
    it divides; heads over `model` when KH divides, otherwise the T dim
    takes `model` (head-count-agnostic sequence sharding); tiny-batch
    (SP) cells additionally spread T over `kv_seq_axis`.  SSM states
    shard their channel/head dim over `model`.
    """
    import jax
    from repro.models import api
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_sz = mesh_shape.get("model", 1)
    cache = api.cache_specs(cfg, shape)
    B = shape.global_batch
    bspec = batch_pspec(plan, B, mesh_shape)[0]

    def div(dim: int, ax: Axis) -> bool:
        if ax is None:
            return False
        axs = (ax,) if isinstance(ax, str) else tuple(ax)
        total = 1
        for a in axs:
            total *= mesh_shape.get(a, 1)
        return dim % total == 0

    def kv_spec(L, Bd, T, KH, hd) -> P:
        b_ax = bspec if div(Bd, bspec) else None
        head_ax = "model" if KH % model_sz == 0 else None
        t_axes = []
        if head_ax is None and T % model_sz == 0:
            t_axes.append("model")
        if b_ax is None and plan.kv_seq_axis is not None \
                and div(T, plan.kv_seq_axis):
            t_axes.append(plan.kv_seq_axis)
        t_ax = tuple(t_axes) if len(t_axes) > 1 else \
            (t_axes[0] if t_axes else None)
        if t_ax is not None and not div(T, t_ax):
            t_ax = None
        return P(None, b_ax, t_ax, head_ax, None)

    def spec_for(path: str, sds) -> P:
        dims = sds.shape
        nd = len(dims)
        leaf = path.split("/")[-1]
        if leaf in ("k", "v", "xk", "xv") and nd == 5:
            return kv_spec(*dims)
        if leaf == "ssm" and nd == 4:             # [L, B, di, N]
            return P(None, bspec if div(dims[1], bspec) else None,
                     "model" if dims[2] % model_sz == 0 else None, None)
        if leaf == "ssm" and nd == 6:             # [NS, I, B, H, P, N]
            return P(None, None, bspec if div(dims[2], bspec) else None,
                     "model" if dims[3] % model_sz == 0 else None,
                     None, None)
        if leaf == "conv" and nd == 4:            # [L, B, W-1, C]
            return P(None, bspec if div(dims[1], bspec) else None, None,
                     "model" if dims[3] % model_sz == 0 else None)
        if leaf == "conv" and nd == 5:            # [NS, I, B, W-1, C]
            return P(None, None, bspec if div(dims[2], bspec) else None,
                     None, "model" if dims[4] % model_sz == 0 else None)
        return P(*([None] * nd))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    specs = []
    for path, sds in flat:
        pstr = "/".join(str(getattr(k, "key", k)) for k in path)
        specs.append(spec_for(pstr, sds))
    return jax.tree_util.tree_unflatten(treedef, specs)
