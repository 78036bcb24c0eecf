"""Sharding policy: the single source of truth for array layouts.

Three ingredients:

* an *active mesh* (module state, entered with :func:`use_mesh`) so model
  code can place sharding constraints without threading a mesh argument
  through every layer — :func:`shard_act` is a no-op when no mesh is
  active, which keeps single-device tests and eager debugging untouched;
* *parameter specs* (:func:`param_specs`): megatron-style tensor
  parallelism over the ``model`` axis plus optional ZeRO-3/FSDP sharding
  over the ``data`` axis, derived from leaf names and shapes;
* *decode-state specs* (:func:`decode_state_specs`): KV caches shard
  batch over ``data`` and KV heads over ``model`` when the head count
  divides the axis; the batch-1 long-context regime instead shards the
  sequence dimension over every mesh axis (context parallelism — the
  only dimension with any parallelism left at batch 1);
* *tensor-parallel serving specs* (:func:`serve_param_specs` /
  :func:`serve_state_specs`): the layout for the serving engines — a
  1-D ``model`` mesh tiling one MVM across devices, PUMA-style.
  :class:`~repro.core.prepack.PackedLinear` weights shard their int8
  differential planes and recombined weight on the N (column-parallel)
  or K (row-parallel, ``_ROW_PARALLEL`` names) axis with scales
  replicated; KV pools and caches shard the KV-head axis.  The serve
  policy is deliberately **bitwise-preserving**: integer contractions
  may split K (partial sums reduce exactly — the inter-tile psum), but
  float weights only ever shard N so every f32 contraction keeps its
  full K, and hence its reduction order, local.

Every constraint carries a divisibility guard: an axis that does not
divide the corresponding dimension is dropped (never an error), so the
same policy serves the (2, 2) test mesh and the (2, 16, 16) production
mesh.
"""
from __future__ import annotations

import contextlib
import threading
from collections.abc import Sequence
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ShardingConfig

_STATE = threading.local()


# ---------------------------------------------------------------------------
# Active-mesh state
# ---------------------------------------------------------------------------

def current_mesh() -> Mesh | None:
    return getattr(_STATE, "mesh", None)


def tp_serving() -> bool:
    """Whether the active mesh is a tensor-parallel *serving* mesh.

    Serving traces (:class:`repro.serve.engine.ServeEngine` and the
    continuous-batching scheduler) enter ``use_mesh(mesh,
    tp_serving=True)``; the flag switches on the bitwise-preserving
    constraint set in ``core.pum_linear`` (:func:`tp_replicate`) without
    touching training/dry-run flows, which never set it.
    """
    return getattr(_STATE, "tp_serving", False)


@contextlib.contextmanager
def use_mesh(mesh: Mesh, *, tp_serving: bool = False):
    """Make ``mesh`` the active mesh for shard_act / param_specs guards.

    ``tp_serving=True`` additionally marks the region as a
    tensor-parallel serving trace (see :func:`tp_serving`).
    """
    prev = current_mesh()
    prev_tp = getattr(_STATE, "tp_serving", False)
    _STATE.mesh = mesh
    _STATE.tp_serving = tp_serving
    try:
        yield mesh
    finally:
        _STATE.mesh = prev
        _STATE.tp_serving = prev_tp


def _axis_sizes(mesh: Mesh) -> dict:
    return dict(mesh.shape)


def _data_axes(mesh: Mesh):
    """The data-parallel axes: ``pod`` acts as extra DP when present."""
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


# ---------------------------------------------------------------------------
# Residual-stream constraint mode (hillclimb knob)
# ---------------------------------------------------------------------------

_SEQ_MODE = "seq"          # "seq" | "hidden" | "batch"


def set_seq_shard(mode) -> None:
    """Set the residual-stream constraint mode.

    Accepts the ``ShardingConfig.seq_shard`` bool (True -> sequence
    parallel, False -> batch only) or an explicit mode string.
    """
    global _SEQ_MODE
    if isinstance(mode, bool):
        mode = "seq" if mode else "batch"
    assert mode in ("seq", "hidden", "batch"), mode
    _SEQ_MODE = mode


def residual_spec() -> tuple[Any, Any, Any]:
    """shard_act axes for the [B, S, D] residual stream.

    Tensor-parallel serving keeps the residual replicated: decode runs
    at S=1 (nothing to sequence-shard) and the bitwise guarantee wants
    every float op outside the linears to see full tensors.
    """
    if tp_serving():
        return (None, None, None)
    return {"seq": ("data", "model", None),
            "hidden": ("data", None, "model"),
            "batch": ("data", None, None)}[_SEQ_MODE]


# ---------------------------------------------------------------------------
# Activation constraints
# ---------------------------------------------------------------------------

def _guard(spec: Sequence[Any], shape: Sequence[int], mesh: Mesh,
           ) -> P:
    """Drop spec axes that are absent from the mesh or do not divide the
    corresponding dimension; expand "data" to the full DP axis group."""
    sizes = _axis_sizes(mesh)
    out = []
    for dim, a in zip(shape, spec):
        if a == "data":
            a = _data_axes(mesh)
        axes = (a,) if isinstance(a, str) else tuple(a or ())
        if not axes or any(ax not in sizes for ax in axes):
            out.append(None)
            continue
        n = int(np.prod([sizes[ax] for ax in axes]))
        out.append(a if n > 0 and dim % n == 0 else None)
    return P(*out)


def shard_act(x: jax.Array, *axes) -> jax.Array:
    """Sharding constraint on an activation; no-op without an active mesh.

    ``axes`` names one mesh axis (or None, or a tuple of axes) per array
    dimension; "data" expands to ("pod", "data") on multi-pod meshes.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    assert len(axes) == x.ndim, (axes, x.shape)
    spec = _guard(axes, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def tp_replicate(x: jax.Array) -> jax.Array:
    """Replicate ``x`` under a tensor-parallel serving trace (else no-op).

    This is the constraint that *closes* a sharded contraction, PUMA's
    inter-tile reduction network in sharding form:

      * placed on the integer accumulator of a row-sharded (K-split)
        ``pum_linear``, XLA lowers it to a psum of the per-shard partial
        MVMs — exact, because the partials are integers (int32, or f32
        within its 24-bit integer window);
      * placed on the input/output of a float (bf16) matmul, it pins the
        contraction to full-K local execution, so the f32 reduction
        order — and hence the bits — match the single-device oracle.
    """
    mesh = current_mesh()
    if mesh is None or not tp_serving():
        return x
    # scoped so the graph auditor can enumerate every closing constraint
    # (rules/accumulators.py dtype-checks the ones under ``tp_accum``)
    with jax.named_scope("tp_replicate"):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*([None] * x.ndim))))


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

# leaf names whose 2D weight is row-parallel (contracted dim carries the
# model-sharded activation, so the *input* dim goes over ``model``)
_ROW_PARALLEL = ("wo", "wd", "out_proj", "down")
# leaf names kept replicated on the model axis (tiny output dims)
_REPLICATED_OUT = ("router", "wi", "wf")


def _leaf_name(path: Sequence[str]) -> str:
    """The linear's name for a param-tree leaf path: the last component
    that isn't the weight/bias key or a stack index."""
    return next((p for p in reversed(tuple(path))
                 if p not in ("w", "b") and not p.isdigit()), "")


def _is_row_parallel(name: str) -> bool:
    return any(name == n or name.endswith(n) for n in _ROW_PARALLEL)


def _leaf_spec(path: tuple[str, ...], shape: Sequence[int],
               scfg: ShardingConfig) -> P:
    fsdp = "data" if scfg.fsdp else None
    stacked = "blocks" in path
    core = shape[1:] if stacked else shape
    name = _leaf_name(path)

    if len(core) <= 1:
        spec: tuple[Any, ...] = (None,) * len(core)
    elif name == "embed":
        spec = ("model", fsdp)
    elif name == "lm_head":
        spec = (fsdp, "model")
    elif name.startswith("experts_"):
        # expert-parallel over model; FSDP over the first matmul dim
        spec = ("model", fsdp) + (None,) * (len(core) - 2)
    elif _is_row_parallel(name):
        spec = ("model", fsdp) + (None,) * (len(core) - 2)
    elif any(name == n for n in _REPLICATED_OUT):
        spec = (fsdp,) + (None,) * (len(core) - 1)
    else:
        # column-parallel default: output dim over model, input over data
        spec = (fsdp,) + (None,) * (len(core) - 2) + ("model",)
    if stacked:
        spec = (None,) + spec
    mesh = current_mesh()
    if mesh is not None:
        return _guard(spec, shape, mesh)
    return P(*spec)


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def param_specs(params: Any, scfg: ShardingConfig | None = None) -> Any:
    """PartitionSpec pytree for a parameter tree (arrays or ShapeDtype-
    Structs).  ``scfg`` defaults to :class:`ShardingConfig` defaults
    (FSDP on), matching the test-suite arity ``param_specs(params)``."""
    scfg = scfg if scfg is not None else ShardingConfig()
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: _leaf_spec(tuple(_key_str(k) for k in kp),
                                    leaf.shape, scfg),
        params)


def named_shardings(mesh: Mesh, specs: Any) -> Any:
    """Map a PartitionSpec pytree to NamedShardings on ``mesh``."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# Decode-state specs
# ---------------------------------------------------------------------------

def decode_state_specs(state: Any, mesh: Mesh) -> Any:
    """Specs for the decode-state tree (group-stacked per-block states).

    Rank-5 leaves are KV caches [groups, batch, seq, kv_heads, head_dim]:
      * batch > 1: batch over ``data``; kv_heads over ``model`` only when
        the head count divides the axis (head-divisibility rule);
      * batch == 1 (long-context serving): no batch parallelism exists, so
        the *sequence* dim shards over every mesh axis instead.
    Recurrent states (rank < 5) shard batch over ``data``; everything
    else stays replicated.
    """
    sizes = _axis_sizes(mesh)
    model_n = sizes.get("model", 1)
    all_axes = tuple(mesh.axis_names)
    total = int(np.prod(mesh.devices.shape))

    def leaf(s) -> P:
        shape = s.shape
        if len(shape) == 5:                      # [G, B, T, KV, hd]
            _, b, t, kv, _ = shape
            if b == 1:
                seq = all_axes if t % total == 0 else None
                return P(None, None, seq, None, None)
            heads = "model" if ("model" in sizes and kv % model_n == 0) \
                else None
            return _guard((None, "data", None, heads, None), shape, mesh)
        if len(shape) >= 2:                      # [G, B, ...] recurrent
            return _guard((None, "data") + (None,) * (len(shape) - 2),
                          shape, mesh)
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map(leaf, state)


# ---------------------------------------------------------------------------
# Tensor-parallel serving specs (ServeEngine / ContinuousBatchingScheduler)
# ---------------------------------------------------------------------------

def packed_linear_specs(packed: Any, row_parallel: bool,
                        mesh: Mesh | None = None) -> Any:
    """PartitionSpec pytree for one :class:`PackedLinear` weight.

    The packed arrays shard the way the crossbar tiling would place
    them (PUMA's MVM-across-tiles decomposition):

      * ``wq`` ``[..., K, N]`` — K over ``model`` for row-parallel
        weights (each shard holds the full output for a K-slice; the
        partial MVMs meet in an exact integer psum), N over ``model``
        otherwise (each shard owns whole output columns);
      * ``planes`` ``[..., S, K, N]`` — same K/N placement with the
        slice axis replicated (every shard keeps all bit-significances
        of its tile, exactly as a crossbar stores all planes of the
        weights it was programmed with);
      * ``scale`` — replicated: it is O(N) bytes and multiplies the
        accumulator *after* the reduction closes.

    Returns a ``PackedLinear``-shaped pytree of specs (same aux
    metadata, so ``jax.device_put(params, named_shardings(mesh, specs))``
    sees matching treedefs).  Divisibility is guarded per-array when a
    mesh is given (or active).
    """
    from repro.core.prepack import PackedLinear
    assert isinstance(packed, PackedLinear), type(packed)
    mesh = mesh or current_mesh()
    lead = packed.wq.ndim - 2                  # stacked group/layer dims
    core = ("model", None) if row_parallel else (None, "model")
    wq = (None,) * lead + core
    scale = (None,) * packed.scale.ndim
    planes = None
    if packed.planes is not None:
        planes = (None,) * lead + (None,) + core        # [..., S, K, N]

    def spec(axes, arr):
        if axes is None:
            return None
        if mesh is not None:
            return _guard(axes, arr.shape, mesh)
        return P(*axes)

    return packed.with_arrays(spec(planes, packed.planes),
                              spec(wq, packed.wq),
                              spec(scale, packed.scale))


def serve_param_specs(params: Any) -> Any:
    """TP-serving PartitionSpec tree over the 1-D ``model`` serving mesh.

    The policy is the bitwise-preserving one the oracle-equivalence
    suite pins (see the module docstring):

      * :class:`PackedLinear` (int8/pum serving weights): row-parallel
        K-sharding for the ``_ROW_PARALLEL`` names, column-parallel N
        elsewhere — integer partial sums reduce exactly;
      * raw float linear weights (bf16 serving, or ``--no-prepack``):
        column-parallel only — float contractions never split K;
      * ``lm_head`` shards the (padded) vocab column axis; the
        embedding table, norms, biases, and every recurrent-cell tensor
        (conv kernels, A-matrices, gates' biases) stay replicated.
    """
    from repro.core.prepack import PackedLinear
    mesh = current_mesh()

    def leaf_spec(path, leaf):
        names = tuple(_key_str(k) for k in path)
        name = _leaf_name(names)
        if isinstance(leaf, PackedLinear):
            return packed_linear_specs(leaf, _is_row_parallel(name), mesh)
        shape = leaf.shape
        if names and names[-1] == "lm_head" and len(shape) == 2:
            spec: tuple[Any, ...] = (None, "model")
        elif names and names[-1] == "w" and len(shape) >= 2:
            # column-parallel: output dim over model, never K (float)
            spec = (None,) * (len(shape) - 1) + ("model",)
        else:
            spec = (None,) * len(shape)
        if mesh is not None:
            return _guard(spec, shape, mesh)
        return P(*spec)

    return jax.tree_util.tree_map_with_path(
        leaf_spec, params,
        is_leaf=lambda v: isinstance(v, PackedLinear))


def serve_state_specs(states: Any, mesh: Mesh | None = None, *,
                      kv_heads: int) -> Any:
    """Specs for a serving decode-state tree (contiguous or paged KV).

    KV storage shards the KV heads over ``model`` (head-divisibility
    guarded): contiguous caches ``[G, B, T, KV, hd]`` on axis 3, paged
    pools ``[G, NB, bs, KV * hd]`` on their folded lane axis — a
    contiguous run of whole heads per shard, only when ``kv_heads``
    divides the axis (``validate_tp`` requires it of a served model) —
    so every device owns the full block pool for its heads and the
    per-row block-table scatter/gather stays device-local.  Recurrent
    rows (xlstm / ssm) and the tiny per-slot lanes stay replicated;
    batch shards over ``data`` when that axis exists (it does not on the
    1-D serving mesh).
    """
    mesh = mesh or current_mesh()
    assert mesh is not None, "serve_state_specs needs a mesh"
    whole_heads = kv_heads % _axis_sizes(mesh).get("model", 1) == 0

    def leaf_spec(path, leaf):
        names = tuple(_key_str(k) for k in path)
        shape = leaf.shape
        if names and names[-1] in ("k_pool", "v_pool"):
            lanes = "model" if whole_heads else None
            return _guard((None, None, None, lanes), shape, mesh)
        if names and names[-1] in ("k", "v") and len(shape) == 5:
            return _guard((None, "data", None, "model", None), shape, mesh)
        spec = ((None, "data") + (None,) * (len(shape) - 2)) \
            if len(shape) >= 2 else (None,) * len(shape)
        return _guard(spec, shape, mesh)

    return jax.tree_util.tree_map_with_path(leaf_spec, states)


def validate_tp(cfg: Any, tp: int) -> None:
    """Raise ``ValueError`` when ``tp`` cannot shard ``cfg`` evenly.

    The spec guards would silently *drop* an indivisible axis (serving
    correct but replicated); a ``--tp`` the model cannot honour should
    fail loudly instead.
    """
    if tp <= 1:
        return
    from repro.models import transformer
    problems = []
    p_len = transformer.period(cfg)
    has_attn = any(transformer.mixer_kind(cfg, j) == "attn"
                   for j in range(p_len))
    if has_attn and cfg.num_kv_heads % tp:
        problems.append(f"num_kv_heads={cfg.num_kv_heads} (KV pool/cache "
                        f"head axis)")
    if cfg.d_model % tp:
        problems.append(f"d_model={cfg.d_model} (column-parallel output "
                        f"axis)")
    if cfg.d_ff and cfg.d_ff % tp:
        problems.append(f"d_ff={cfg.d_ff} (MLP column axis)")
    if problems:
        raise ValueError(
            f"tensor parallelism tp={tp} does not divide "
            + "; ".join(problems)
            + f" for model '{cfg.name}'; pick a tp that divides every "
              f"sharded axis")
