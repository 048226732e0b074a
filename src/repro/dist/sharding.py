"""Logical-axis sharding rules and the ambient activation-sharding context.

Model code never names mesh axes. Parameters carry LOGICAL axis names in
their ``ParamSpec.axes`` (``"embed"``, ``"ffn"``, ``"vocab"``, ...);
activations are constrained through :func:`constrain_batch` /
:func:`constrain_logical`. This module owns the single mapping from
logical names to mesh axes (:class:`ShardingRules`) and derives concrete
``PartitionSpec``s from it, with three safety rules applied in order:

  1. axes absent from the mesh are dropped (a single-pod mesh has no
     ``"pod"`` axis — ``act_batch = ("pod", "data")`` degrades to
     ``("data",)``),
  2. a mesh axis is never used twice in one spec (first dim wins),
  3. a dim that is not divisible by the prospective axis-size product is
     progressively relaxed by dropping trailing axes, down to replicated.

The ambient context (:func:`activation_sharding`) carries
``(mesh, dp_axes, seq_axis)`` so that pure model functions can constrain
intermediate activations without threading the mesh through every call.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Callable, NamedTuple, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "ShardingRules",
    "DEFAULT_RULES",
    "FSDP_POD_RULES",
    "PURE_DP_RULES",
    "SP_DECODE_RULES",
    "logical_to_pspec",
    "batch_pspec",
    "make_sharding_fn",
    "abstract_state",
    "activation_sharding",
    "constrain_batch",
    "constrain_logical",
]

# A logical axis maps to: None (replicated), one mesh axis, or an ordered
# tuple of mesh axes (sharded over their product).
AxisRule = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Logical-axis -> mesh-axis mapping. One field per logical axis."""

    # parameter axes
    embed: AxisRule = None         # d_model rows (FSDP axis by default)
    embed_out: AxisRule = None     # d_model columns of square projections
    vocab: AxisRule = None
    ffn: AxisRule = None
    ffn_out: AxisRule = None
    heads: AxisRule = None
    head_dim: AxisRule = None
    kv_heads: AxisRule = None
    kv_lora: AxisRule = None       # MLA latent dims
    q_lora: AxisRule = None
    expert: AxisRule = None        # MoE expert dim (EP axis)
    expert_ffn: AxisRule = None
    ssm_heads: AxisRule = None
    ssm_inner: AxisRule = None
    layers: AxisRule = None        # stacked-segment leading dim
    # activation / cache axes
    act_batch: AxisRule = None
    act_kv_seq: AxisRule = None

    def get(self, name: str) -> AxisRule:
        return getattr(self, name, None)

    def replace(self, **kwargs) -> "ShardingRules":
        return dataclasses.replace(self, **kwargs)


# FSDP over the data axis + tensor parallelism over the model axis. The
# batch shards over (pod, data) — the fastest-k worker grain.
DEFAULT_RULES = ShardingRules(
    embed="data",
    embed_out="model",
    vocab="model",
    ffn="model",
    ffn_out="model",
    heads="model",
    kv_heads="model",
    expert="model",
    ssm_heads="model",
    ssm_inner="model",
    act_batch=("pod", "data"),
)

# Pod-wide ZeRO: FSDP axis spans (pod, data) — for the largest configs.
FSDP_POD_RULES = DEFAULT_RULES.replace(embed=("pod", "data"))

# Sequence-parallel KV caches for distributed flash-decode.
SP_DECODE_RULES = DEFAULT_RULES.replace(act_kv_seq="model")

# Pure data parallelism: params replicated, batch over every mesh axis.
PURE_DP_RULES = ShardingRules(act_batch=("pod", "data", "model"))


def _axis_sizes(mesh) -> dict:
    # Works for both jax.sharding.Mesh and lightweight test stubs: only
    # ``mesh.shape`` (an axis-name -> size mapping) is required.
    return dict(mesh.shape)


def _fit_axes(
    candidate: Sequence[str], dim: int, sizes: dict, used: set
) -> Tuple[str, ...]:
    """Filter a candidate mesh-axis tuple against the mesh (rules 1-3)."""
    cand = tuple(a for a in candidate if a in sizes and a not in used)
    def prod(axes):
        p = 1
        for a in axes:
            p *= sizes[a]
        return p
    while cand and dim % prod(cand) != 0:
        cand = cand[:-1]
    return cand


def logical_to_pspec(
    axes: Sequence[Optional[str]],
    shape: Sequence[int],
    mesh,
    rules: ShardingRules,
) -> P:
    """Derive a PartitionSpec for one array from its logical axes."""
    sizes = _axis_sizes(mesh)
    used: set = set()
    entries = []
    for name, dim in zip(axes, shape):
        entry = None
        rule = rules.get(name) if name is not None else None
        if rule is not None:
            cand = _fit_axes((rule,) if isinstance(rule, str) else rule,
                             dim, sizes, used)
            if cand:
                used.update(cand)
                entry = cand[0] if len(cand) == 1 else cand
        entries.append(entry)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def batch_pspec(
    mesh, batch: int, n_trailing: int = 0, *, dp_axes: Optional[Sequence[str]] = None
) -> P:
    """PartitionSpec sharding dim 0 (the batch) over the data-parallel
    axes, with ``n_trailing`` replicated trailing dims."""
    sizes = _axis_sizes(mesh)
    cand = _fit_axes(tuple(dp_axes) if dp_axes is not None else ("pod", "data"),
                     batch, sizes, set())
    entry = None if not cand else (cand[0] if len(cand) == 1 else cand)
    if entry is None:
        return P()
    return P(entry, *(None,) * n_trailing)


def make_sharding_fn(
    mesh, rules: Optional[ShardingRules] = None
) -> Callable[[object], NamedSharding]:
    """Returns ``spec -> NamedSharding`` for ParamSpec-like objects
    (anything with ``.axes`` and ``.shape``)."""
    rules = DEFAULT_RULES if rules is None else rules

    def sharding_for(spec) -> NamedSharding:
        return NamedSharding(
            mesh, logical_to_pspec(spec.axes, spec.shape, mesh, rules)
        )

    return sharding_for


def abstract_state(model, mesh, rules: ShardingRules, optimizer=None):
    """Abstract (params, opt_state) with production shardings attached.
    ``model`` is anything with ``abstract_params(sharding_fn)``."""
    params = model.abstract_params(make_sharding_fn(mesh, rules))
    if optimizer is None:
        return params, None
    opt_state = jax.eval_shape(optimizer.init, params)

    # eval_shape loses shardings; attach by matching shapes against params.
    # Exact-shape matches cover adam m/v; adafactor factored rows
    # (p.shape[:-1]) and cols (p.shape[:-2] + p.shape[-1:]) inherit the
    # param's pspec with the corresponding dim removed.
    param_leaves = jax.tree.leaves(params)
    by_shape = {}
    row_shapes = {}
    col_shapes = {}
    for p in param_leaves:
        by_shape.setdefault(p.shape, p.sharding)
        spec = tuple(p.sharding.spec) + (None,) * (len(p.shape) - len(p.sharding.spec))
        if len(p.shape) >= 2:
            row_shapes.setdefault(p.shape[:-1], P(*spec[:-1]))
            col_shapes.setdefault(
                p.shape[:-2] + p.shape[-1:], P(*(spec[:-2] + spec[-1:]))
            )

    def attach(x):
        if not hasattr(x, "shape"):
            return x
        sh = by_shape.get(x.shape)
        if sh is None and x.shape in row_shapes:
            sh = NamedSharding(mesh, row_shapes[x.shape])
        if sh is None and x.shape in col_shapes:
            sh = NamedSharding(mesh, col_shapes[x.shape])
        if sh is None:
            sh = NamedSharding(mesh, P())
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)

    opt_state = jax.tree.map(attach, opt_state)
    return params, opt_state


# ---------------------------------------------------------------------------
# Ambient activation-sharding context
# ---------------------------------------------------------------------------

# ActContext or None. Model code reads this through constrain_batch /
# constrain_logical; repro.models.moe reads it directly to size its
# data-parallel dispatch groups.
class ActContext(NamedTuple):
    mesh: object
    dp: Tuple[str, ...]
    seq_axis: Optional[str]
    rules: ShardingRules


_ACT_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "repro_dist_act_ctx", default=None
)


@contextlib.contextmanager
def activation_sharding(
    mesh,
    *,
    seq_axis: Optional[str] = None,
    dp_axes: Optional[Sequence[str]] = None,
    rules: Optional[ShardingRules] = None,
):
    """Install the ambient mesh context for activation constraints.

    ``dp_axes``: mesh axes the batch dim shards over (default: whichever
    of ``("pod", "data")`` the mesh has). ``seq_axis``: optional mesh
    axis for Megatron-style sequence-parallel activations. ``rules``:
    the ShardingRules used to resolve parameter-style logical names in
    :func:`constrain_logical` (default DEFAULT_RULES) — pass the run's
    active rules so activation constraints follow rule overrides.
    """
    sizes = _axis_sizes(mesh)
    if dp_axes is None:
        dp = tuple(a for a in ("pod", "data") if a in sizes)
    else:
        dp = tuple(a for a in dp_axes if a in sizes)
    token = _ACT_CTX.set(
        ActContext(mesh, dp, seq_axis, DEFAULT_RULES if rules is None else rules)
    )
    try:
        yield
    finally:
        _ACT_CTX.reset(token)


def _constrain(x, entries, mesh):
    while entries and entries[-1] is None:
        entries.pop()
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*entries)))


def constrain_batch(x):
    """Constrain an activation's dim 0 to the ambient data-parallel axes
    (and dim 1 to the ambient sequence axis, when set). No-op outside an
    :func:`activation_sharding` context — model code stays runnable on a
    single device."""
    ctx = _ACT_CTX.get()
    if ctx is None:
        return x
    mesh, dp, seq_axis = ctx.mesh, ctx.dp, ctx.seq_axis
    sizes = _axis_sizes(mesh)
    used: set = set()
    cand = _fit_axes(dp, x.shape[0], sizes, used)
    entries: list = [None if not cand else (cand[0] if len(cand) == 1 else cand)]
    used.update(cand)
    if x.ndim >= 2 and seq_axis is not None:
        seq = _fit_axes((seq_axis,), x.shape[1], sizes, used)
        entries.append(seq[0] if seq else None)
    return _constrain(x, entries, mesh)


def constrain_logical(x, axes: Sequence[Optional[str]]):
    """Constrain an activation by logical axis names under the ambient
    context. ``act_batch`` resolves to the ambient dp axes and
    ``act_kv_seq`` to the ambient sequence axis; parameter-style names
    (``expert``, ``heads``, ...) resolve through the ambient context's
    ShardingRules. No-op outside an :func:`activation_sharding` context."""
    ctx = _ACT_CTX.get()
    if ctx is None:
        return x
    mesh, dp, seq_axis = ctx.mesh, ctx.dp, ctx.seq_axis
    sizes = _axis_sizes(mesh)
    used: set = set()
    entries = []
    for name, dim in zip(axes, x.shape):
        if name == "act_batch":
            rule: AxisRule = dp
        elif name == "act_kv_seq":
            rule = seq_axis
        elif name is not None:
            rule = ctx.rules.get(name)
        else:
            rule = None
        entry = None
        if rule:
            cand = _fit_axes((rule,) if isinstance(rule, str) else rule,
                             dim, sizes, used)
            if cand:
                used.update(cand)
                entry = cand[0] if len(cand) == 1 else cand
        entries.append(entry)
    return _constrain(x, entries, mesh)
