"""One Partitioner for the whole mesh: logical axes → mesh axes.

Model code names array dimensions by *meaning* — ``embed``, ``mlp``,
``heads``, ``kv``, ``vocab``, ``expert``, ``stage``, ``batch``, ``seq`` —
and this module owns the single table mapping those meanings onto mesh
axis names (``slice_``, ``pp``, ``dp``, ``sp``, ``tp``, ``ep``). Before
this existed every model family hand-wired ``P(...)`` trees (13 ``P(``
sites in gpt.py alone) and each ``parallel/`` module grew its own mesh
plumbing; now a spec is data (a tuple of logical names per array dim) and
policy lives in one rule table per family, T5X-style (SNIPPETS [2]/[3]).

Two entry points:

* :func:`resolve_specs` + :func:`rules_from_axes` — the low-level pair
  the model modules use so their historical ``*_param_specs(cfg,
  tp_axis)`` signatures survive as thin wrappers over logical trees.
* :class:`Partitioner` — mesh + family rules in one object. Training
  factories build one per mesh and pull param specs, optimizer-state
  specs, batch specs and axis names from it instead of consulting the
  mesh by hand.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from byteps_tpu.parallel.mesh import MeshAxes, factor_devices, make_mesh

#: A rule target: where one logical axis lands on the mesh. ``None``
#: replicates; a tuple shards over several mesh axes (outermost first).
AxisTarget = Union[None, str, Tuple[Optional[str], ...]]

#: The logical vocabulary. Model logical trees may only use these names
#: (or ``None`` for an always-replicated dim).
LOGICAL_AXES = ("batch", "seq", "embed", "mlp", "heads", "kv", "vocab",
                "expert", "stage")

_BASE_RULES: Dict[str, AxisTarget] = {
    "batch": ("slice_", "dp"),   # data parallel: DCN outermost, then ICI dp
    "seq": "sp",                 # sequence/context parallel (ring attention)
    "embed": None,               # residual stream stays replicated
    "mlp": "tp",                 # Megatron col/row: ffn hidden over tp
    "heads": "tp",               # attention heads over tp
    "kv": "tp",                  # kv heads over tp (= heads unless GQA)
    "vocab": None,               # embedding / readout replicated
    "expert": "ep",              # MoE expert dim
    "stage": "pp",               # pipeline stage dim (stacked blocks)
}

#: Per-model-family rule tables. All families currently share the
#: Megatron-ish base; they are separate dicts so a family can diverge
#: (e.g. moe_gpt folds ep into the batch axis — tokens ride the expert
#: axis as extra data parallelism outside the MoE blocks).
FAMILY_RULES: Dict[str, Dict[str, AxisTarget]] = {
    "gpt": dict(_BASE_RULES),
    "bert": dict(_BASE_RULES),
    "t5": dict(_BASE_RULES),
    "vit": dict(_BASE_RULES),
    "resnet": dict(_BASE_RULES),
    "moe_gpt": {**_BASE_RULES, "batch": ("slice_", "dp", "ep")},
}

#: Which logical dims a data batch carries, per family.
FAMILY_BATCH_DIMS: Dict[str, Tuple[str, ...]] = {
    "gpt": ("batch", "seq"),
    "bert": ("batch", "seq"),
    "t5": ("batch", "seq"),
    "moe_gpt": ("batch", "seq"),
    "vit": ("batch",),
    "resnet": ("batch",),
}


def _is_logical_leaf(node: Any) -> bool:
    return isinstance(node, tuple) and all(
        n is None or isinstance(n, str) for n in node)


def _filter_target(target: AxisTarget,
                   axis_names: Optional[Sequence[str]]) -> AxisTarget:
    """Drop ``None`` entries and (when ``axis_names`` given) mesh axes
    that don't exist; collapse to a bare name / ``None`` when possible."""
    if target is None:
        return None
    if isinstance(target, str):
        target = (target,)
    present = tuple(a for a in target
                    if a is not None
                    and (axis_names is None or a in axis_names))
    if not present:
        return None
    return present if len(present) > 1 else present[0]


def resolve_spec(logical: Tuple[Optional[str], ...],
                 rules: Mapping[str, AxisTarget],
                 axis_names: Optional[Sequence[str]] = None) -> P:
    """One logical leaf → a PartitionSpec.

    ``axis_names`` (usually ``mesh.axis_names``) filters rule targets to
    axes that actually exist; pass ``None`` to trust the rules as given
    (the model-module wrapper path, where the caller already passed
    ``tp_axis=None`` for a tp-less mesh). An all-replicated leaf
    canonicalizes to ``P()``.
    """
    entries = []
    for name in logical:
        if name is None:
            entries.append(None)
            continue
        if name not in LOGICAL_AXES:
            raise ValueError(f"unknown logical axis {name!r}; "
                             f"expected one of {LOGICAL_AXES}")
        entries.append(_filter_target(rules.get(name), axis_names))
    if all(e is None for e in entries):
        return P()
    return P(*entries)


def resolve_specs(logical_tree: Any, rules: Mapping[str, AxisTarget],
                  axis_names: Optional[Sequence[str]] = None) -> Any:
    """Map :func:`resolve_spec` over a pytree whose leaves are logical
    tuples (one entry per array dim)."""
    return jax.tree.map(
        lambda leaf: resolve_spec(leaf, rules, axis_names),
        logical_tree, is_leaf=_is_logical_leaf)


def stacked_logical_specs(logical_tree: Any) -> Any:
    """Prepend the ``stage`` logical axis to every leaf — the logical
    analog of :func:`byteps_tpu.parallel.pipeline.stacked_specs` for a
    pipeline slab stacked on a leading layer axis."""
    return jax.tree.map(lambda t: ("stage",) + t, logical_tree,
                        is_leaf=_is_logical_leaf)


def rules_from_axes(tp_axis: Optional[str] = None,
                    sp_axis: Optional[str] = None,
                    dp_axis: Optional[str] = None,
                    ep_axis: Optional[str] = None,
                    pp_axis: Optional[str] = None,
                    slice_axis: Optional[str] = None
                    ) -> Dict[str, AxisTarget]:
    """Rule table from explicit axis names — the compatibility bridge for
    the historical ``*_param_specs(cfg, tp_axis)`` signatures, where the
    caller resolved axis presence before calling."""
    return {
        "batch": (slice_axis, dp_axis),
        "seq": sp_axis,
        "embed": None,
        "mlp": tp_axis,
        "heads": tp_axis,
        "kv": tp_axis,
        "vocab": None,
        "expert": ep_axis,
        "stage": pp_axis,
    }


def _logical_specs_for(cfg: Any, params: Any = None) -> Any:
    """Dispatch a model config to its family's logical spec tree."""
    name = type(cfg).__name__
    if name == "GPTConfig":
        from byteps_tpu.models.gpt import gpt_logical_specs
        return gpt_logical_specs(cfg)
    if name == "MoEGPTConfig":
        from byteps_tpu.models.moe_gpt import moe_gpt_logical_specs
        return moe_gpt_logical_specs(cfg)
    if name == "JoyAIConfig":
        from byteps_tpu.models.joyai import joyai_logical_specs
        return joyai_logical_specs(cfg)
    if name == "T5Config":
        from byteps_tpu.models.t5 import t5_logical_specs
        return t5_logical_specs(cfg)
    if name == "BertConfig":
        from byteps_tpu.models.bert import bert_logical_specs
        return bert_logical_specs(cfg)
    if name == "ViTConfig":
        from byteps_tpu.models.vit import vit_logical_specs
        return vit_logical_specs(cfg)
    if name == "ResNetConfig":
        from byteps_tpu.models.resnet import resnet_logical_specs
        if params is None:
            raise ValueError("resnet logical specs need the params tree")
        return resnet_logical_specs(cfg, params)
    raise TypeError(f"no logical-spec table for config type {name}")


_FAMILY_BY_CONFIG = {
    "GPTConfig": "gpt", "MoEGPTConfig": "moe_gpt", "JoyAIConfig": "moe_gpt",
    "T5Config": "t5",
    "BertConfig": "bert", "ViTConfig": "vit", "ResNetConfig": "resnet",
}


@dataclasses.dataclass
class Partitioner:
    """Mesh + logical-axis rules in one object.

    Everything a training/serving factory needs from the topology flows
    through here: mesh axis names (``.dp``/``.tp``/...), param specs
    (:meth:`param_specs`), optimizer-state specs (:meth:`opt_state_specs`)
    and batch specs/shardings (:meth:`batch_spec`, :meth:`batch_sharding`).
    """

    mesh: Mesh
    family: str = "gpt"
    overrides: Optional[Mapping[str, AxisTarget]] = None

    def __post_init__(self):
        base = FAMILY_RULES.get(self.family)
        if base is None:
            raise ValueError(f"unknown model family {self.family!r}; "
                             f"have {sorted(FAMILY_RULES)}")
        self.rules: Dict[str, AxisTarget] = dict(base)
        if self.overrides:
            self.rules.update(self.overrides)

    # -- construction --------------------------------------------------
    @classmethod
    def create(cls, axes: Optional[MeshAxes] = None, family: str = "gpt",
               devices: Optional[Sequence] = None,
               num_slices: int = 1, **factor_kw) -> "Partitioner":
        """Build mesh and partitioner together. With ``axes=None`` the
        device count is factored heuristically (:func:`factor_devices`)."""
        if devices is None:
            devices = jax.devices()
        if axes is None:
            axes = factor_devices(len(devices), n_slices=num_slices,
                                  **factor_kw)
        return cls(make_mesh(axes, devices=devices), family=family)

    @classmethod
    def for_config(cls, cfg: Any, mesh: Mesh,
                   overrides: Optional[Mapping[str, AxisTarget]] = None
                   ) -> "Partitioner":
        family = _FAMILY_BY_CONFIG.get(type(cfg).__name__)
        if family is None:
            raise TypeError(f"no family for config type {type(cfg).__name__}")
        return cls(mesh, family=family, overrides=overrides)

    # -- mesh axis accessors -------------------------------------------
    def _axis(self, name: str) -> Optional[str]:
        return name if name in self.mesh.axis_names else None

    @property
    def dp(self) -> Optional[str]:
        return self._axis("dp")

    @property
    def tp(self) -> Optional[str]:
        return self._axis("tp")

    @property
    def sp(self) -> Optional[str]:
        return self._axis("sp")

    @property
    def pp(self) -> Optional[str]:
        return self._axis("pp")

    @property
    def ep(self) -> Optional[str]:
        return self._axis("ep")

    @property
    def slice_(self) -> Optional[str]:
        return self._axis("slice_")

    def axis_size(self, name: str) -> int:
        return self.mesh.shape[name] if name in self.mesh.axis_names else 1

    def mesh_axes(self, logical: str) -> AxisTarget:
        """Mesh axis (or axes) one logical axis lands on, filtered to
        axes present in this mesh. ``None`` → replicated."""
        if logical not in LOGICAL_AXES:
            raise ValueError(f"unknown logical axis {logical!r}")
        return _filter_target(self.rules.get(logical),
                              self.mesh.axis_names)

    def batch_axes(self) -> AxisTarget:
        """Mesh axes the batch dim is split over — what loss functions
        pmean over and the gradient reduction runs over."""
        return self.mesh_axes("batch")

    # -- specs ----------------------------------------------------------
    def spec(self, *logical: Optional[str]) -> P:
        return resolve_spec(tuple(logical), self.rules,
                            self.mesh.axis_names)

    def resolve(self, logical_tree: Any) -> Any:
        return resolve_specs(logical_tree, self.rules,
                             self.mesh.axis_names)

    def param_specs(self, cfg: Any, params: Any = None) -> Any:
        """PartitionSpec tree for a model config's params (resnet also
        needs the params tree — its shape depends on stage widths)."""
        return self.resolve(_logical_specs_for(cfg, params))

    def opt_state_specs(self, opt_state: Any, params: Any,
                        param_specs: Any) -> Any:
        from byteps_tpu.parallel.sharding import opt_state_specs
        return opt_state_specs(opt_state, params, param_specs)

    def batch_spec(self, dims: Optional[Tuple[str, ...]] = None) -> P:
        if dims is None:
            dims = FAMILY_BATCH_DIMS[self.family]
        return self.spec(*dims)

    def batch_sharding(self, dims: Optional[Tuple[str, ...]] = None
                       ) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec(dims))

    def param_sharding(self, cfg: Any, params: Any = None) -> Any:
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.param_specs(cfg, params))
