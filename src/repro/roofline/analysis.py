"""Roofline analysis from compiled dry-run artifacts (TPU v5e model).

Terms (per the brief):
  compute term    = HLO_FLOPs_global / (chips * peak_FLOP/s)
  memory term     = HLO_bytes_global / (chips * HBM_bw)
  collective term = collective_bytes_per_device / link_bw

``compiled.cost_analysis()`` reports the PER-DEVICE SPMD program, so the
global quantities are per-device x chips and the chips factor cancels:
compute term = flops_per_device / peak. Collective bytes are parsed from the
compiled HLO text (not in cost_analysis): we sum the RESULT-shape bytes of
every all-gather / all-reduce / all-to-all / reduce-scatter /
collective-permute instruction in the per-device module.
"""
from __future__ import annotations

import dataclasses
import re

V5E = {
    "peak_flops": 197e12,  # bf16 per chip
    "hbm_bw": 819e9,  # B/s
    "ici_bw": 50e9,  # B/s per link
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(.*?)\s+"
    r"(all-gather|all-reduce|all-to-all|reduce-scatter|collective-permute)"
    r"(-start)?\("
)

_SHAPE_RE = re.compile(
    r"(bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64|pred|c64|c128)"
    r"\[([0-9,]*)\]"
)


def shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_tuple_elements(shape_str: str) -> list[str]:
    """Top-level elements of an HLO tuple shape string, or [] when the
    string is not a parenthesized tuple. Layout braces ``{1,0}`` and nested
    tuples are kept intact (commas inside either never split)."""
    s = shape_str.strip()
    if not (s.startswith("(") and s.endswith(")")):
        return []
    parts, depth, buf = [], 0, []
    for ch in s[1:-1]:
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(buf))
            buf = []
        else:
            buf.append(ch)
    if buf:
        parts.append("".join(buf))
    return [p.strip() for p in parts]


def _is_context_scalar(element: str) -> bool:
    """Async collectives append u32[]/s32[] context scalars to the -start
    tuple; they carry no payload and must not count as collective bytes."""
    return re.fullmatch(r"[su]32\[\]\S*", element) is not None


def _start_result_bytes(shape_str: str) -> int:
    """Payload bytes of an async ``-start`` instruction.

    The -start shape is a tuple ``(operand(s), result(s), context...)`` —
    counting the whole tuple double-counts the payload (operand aliases) and
    adds the u32[] contexts. Only the result portion (second non-context
    top-level element; itself possibly a tuple, e.g. all-to-all-start)
    carries the bytes the link actually moves.
    """
    elements = [e for e in _split_tuple_elements(shape_str)
                if not _is_context_scalar(e)]
    if not elements:  # not a tuple: count the shape as-is
        return shape_bytes(shape_str)
    result = elements[1] if len(elements) >= 2 else elements[0]
    return shape_bytes(result)


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes by collective kind, from the compiled SPMD module.

    Line-based (one HLO instruction per line). Sync collectives count their
    full result shape (a tuple result, e.g. decomposed all-to-all, sums all
    elements). Async ``-start`` halves count only the result portion of the
    start tuple — the operand aliases and u32[] context scalars in
    ``(operand, result, context...)`` are bookkeeping, not payload — and the
    ``-done`` halves are excluded entirely so starts aren't double-counted.
    """
    out = {"all-gather": 0, "all-reduce": 0, "all-to-all": 0,
           "reduce-scatter": 0, "collective-permute": 0}
    counts = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shapes, kind, is_start = m.group(1), m.group(2), bool(m.group(3))
        out[kind] += _start_result_bytes(shapes) if is_start \
            else shape_bytes(shapes)
        counts[kind] += 1
    return {"bytes": out, "counts": counts, "total": sum(out.values())}


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float

    def to_dict(self):
        return dataclasses.asdict(self)


def roofline_terms(flops_per_device: float, bytes_per_device: float,
                   coll_bytes_per_device: float, hw=V5E) -> Roofline:
    ct = flops_per_device / hw["peak_flops"]
    mt = bytes_per_device / hw["hbm_bw"]
    lt = coll_bytes_per_device / hw["ici_bw"]
    terms = {"compute": ct, "memory": mt, "collective": lt}
    dom = max(terms, key=terms.get)
    return Roofline(ct, mt, lt, dom, flops_per_device, bytes_per_device,
                    coll_bytes_per_device)


def useful_flops(arch: str, shape_name: str, mode: str, cfg, shape) -> float:
    """MODEL_FLOPS: 6*N*D for LM train (N params w/o embeddings, D tokens);
    2*N_active*D for decode/prefill-token; family-appropriate analogues
    elsewhere (documented in EXPERIMENTS.md)."""
    if cfg.family == "lm":
        d, L = cfg.d_model, cfg.n_layers
        hd = cfg.hd
        attn = d * (cfg.n_heads + 2 * cfg.n_kv_heads) * hd + cfg.n_heads * hd * d
        if cfg.moe is not None:
            mo = cfg.moe
            ffn_active = 3 * d * (mo.d_ff_expert * mo.top_k
                                  + (mo.d_ff_shared or 0))
            n_dense = mo.first_dense
            n_active = (L - n_dense) * (attn + ffn_active) \
                + n_dense * (attn + 3 * d * (mo.d_ff_dense or cfg.d_ff))
        else:
            n_active = L * (attn + 3 * d * cfg.d_ff)
        n_active += d * cfg.vocab  # lm head
        tokens = shape.d("global_batch") * (shape.d("seq_len") if mode != "decode"
                                            else 1)
        if mode == "train":
            return 6.0 * n_active * tokens
        if mode == "prefill":
            return 2.0 * n_active * tokens
        # decode also reads the KV cache: attention scores 2*B*S*H*hd*2
        kv = 4.0 * shape.d("global_batch") * shape.d("seq_len") \
            * cfg.n_heads * hd * L
        return 2.0 * n_active * tokens + kv
    if cfg.family == "recsys":
        d = cfg.embed_dim
        b = shape.d("batch")
        s = cfg.seq_len
        per_tok = cfg.n_blocks * (4 * d * d + 2 * cfg.d_ff_mult * d * d
                                  + 2 * s * d)
        flops = 2.0 * b * s * per_tok
        if mode == "train":
            flops *= 3
            flops += 6.0 * b * s * d * (cfg.n_items + 2) * 0  # masked subset
            flops += 6.0 * b * s * d  # embedding
            flops += 6.0 * b * s * (cfg.n_items + 2) * d * 0.2  # masked lm head
        elif mode == "retrieval":
            flops += 2.0 * shape.d("n_candidates") * d
        else:
            flops += 2.0 * b * d * (cfg.n_items + 2)
        return flops
    if cfg.family == "gnn":
        n, e = shape.d("n_nodes", 1), shape.d("n_edges", 1)
        if shape.name == "minibatch_lg":
            from repro.data.graphs import sampled_sizes

            n, e = sampled_sizes(shape.d("batch_nodes"),
                                 (shape.d("fanout1"), shape.d("fanout2")))
        if shape.name == "molecule":
            n, e = n * shape.d("batch"), e * shape.d("batch")
        d = cfg.d_hidden
        L = cfg.n_layers
        train_mult = 3.0  # fwd + bwd
        if cfg.kind == "graphsage":
            per_layer = 2 * e * d + 4 * n * d * d
        elif cfg.kind == "dimenet":
            from repro.data.graphs import TRIPLET_FACTOR

            p_tri = TRIPLET_FACTOR * e
            nb = cfg.opt("n_bilinear", 8)
            per_layer = 2 * p_tri * nb * d * d / 8 + 8 * e * d * d
        elif cfg.kind == "equiformer_v2":
            k_comp = (cfg.opt("l_max", 6) + 1) ** 2
            per_layer = 2 * e * k_comp * d * d + 4 * e * d * d
        else:  # graphcast: processor on the MESH edges
            from repro.data.graphs import graphcast_sizes

            sz = graphcast_sizes(n)
            per_layer = 2 * sz["e_mesh"] * 8 * d * d
        return train_mult * L * per_layer
    if cfg.family == "matching":
        # per AWAC round: relabel+join O(m log m) + O(n) selection
        import math

        n = shape.d("n")
        m = n * shape.d("avg_degree")
        return (m * (2 + math.log2(max(m, 2))) + 8 * n) * 8
    return 0.0
