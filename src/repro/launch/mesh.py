"""Production mesh + per-family sharding rules.

Importing this module never touches jax device state (the mesh is built by a
FUNCTION, per the dry-run contract)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def all_axes(mesh) -> tuple:
    return tuple(mesh.axis_names)


def _divisible(dim: int, mesh, axes) -> bool:
    import numpy as np

    k = int(np.prod([mesh.shape[a] for a in (axes if isinstance(axes, tuple)
                                             else (axes,))]))
    return dim % k == 0


def lm_param_rules(cfg, mesh, variants=()) -> dict:
    """Logical-axis -> mesh-axis rules for LM parameter trees.
    TP over "model" (heads / mlp / vocab), FSDP over "data" (embed dim),
    EP over "pod" when the expert count divides; the "moe_ep" variant moves
    EP onto the "model" axis (expert_mlp then stays unsharded)."""
    rules = {
        "vocab": "model",
        "mlp": "model",
        "expert_mlp": "model",
        "embed": "data" if cfg.d_model % mesh.shape["data"] == 0 else None,
        "heads": "model" if _divisible(cfg.n_heads * cfg.hd, mesh, "model")
        else None,
        "kv_heads": "model"
        if _divisible(cfg.n_kv_heads * cfg.hd, mesh, "model") else None,
        "experts": None,
    }
    if cfg.moe is not None:
        if any(str(v).startswith("moe_ep") for v in variants) \
                and cfg.moe.n_experts % mesh.shape["model"] == 0:
            rules["experts"] = "model"
            rules["expert_mlp"] = None
        elif "pod" in mesh.axis_names \
                and cfg.moe.n_experts % mesh.shape["pod"] == 0:
            rules["experts"] = "pod"
    return rules


def gnn_param_rules(cfg, mesh) -> dict:
    d = cfg.d_hidden
    ok = d % mesh.shape["model"] == 0
    return {"embed": None, "mlp": "model" if ok else None, "experts": None,
            "vocab": None, "heads": None, "kv_heads": None}


def recsys_param_rules(cfg, mesh) -> dict:
    # tables are row-sharded over "model"; batch uses ALL axes (B >> d)
    return {"vocab": "model", "embed": None, "mlp": None, "heads": None,
            "kv_heads": None, "experts": None}


def matching_rules(mesh) -> dict:
    return {}


def param_rules_for(cfg, mesh) -> dict:
    return {
        "lm": lm_param_rules,
        "gnn": gnn_param_rules,
        "recsys": recsys_param_rules,
    }[cfg.family](cfg, mesh)
