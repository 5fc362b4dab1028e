"""Kernel micro-benchmarks: Pallas (interpret mode — correctness-grade
timings on CPU; the TPU perf story lives in the roofline analysis) vs jnp
reference, plus arithmetic-intensity derivations for the v5e roofline, plus
the end-to-end AWAC iterations/sec contest between the seed implementation
and the fused sparse sweep engine (DESIGN.md §3)."""
import datetime

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._util import row, time_call
from repro.kernels import dispatch as kdispatch
from repro.kernels.backend import resolve_execution
from repro.kernels.cycle_gain import cycle_gain_padded, cycle_gain_ref
from repro.kernels.embedding_bag import embedding_bag_padded, embedding_bag_ref
from repro.kernels.flash_attention import attention_ref, flash_attention


def _mode_note(backend: str) -> str:
    """``;interpret=`` annotation for Pallas rows — an interpreter timing
    must never be mistakable for a compiled-kernel timing."""
    if not backend.startswith("pallas"):
        return ""
    return ";" + resolve_execution(None).describe()


def _backends() -> tuple[str, ...]:
    """The measured AWAC backends the current platform can compile (on a
    TPU, neither Pallas sweep has a Mosaic lowering)."""
    from repro.core.single import backend_available

    return tuple(b for b in kdispatch.MEASURED_BACKENDS
                 if backend_available(b))


def _measure_awac_single(n: int, avg_degree: float, seed: int = 0,
                         emit_rows: bool = False):
    """Per-iteration AWAC time for every local backend on one synthetic
    instance. Returns ({backend: us_per_iter}, {pallas backend: interpret},
    {backend: weight})."""
    from repro.core import graph, single

    g = graph.generate(n, avg_degree=avg_degree, kind="uniform", seed=seed)
    r, c, v = jnp.asarray(g.row), jnp.asarray(g.col), jnp.asarray(g.val)
    st = single.greedy_maximal(r, c, v, g.n)
    st = single.mcm(r, c, v, g.n, st.mate_row, st.mate_col)

    us, interp, weights = {}, {}, {}
    for backend in _backends():
        dt, (stf, iters) = time_call(
            lambda b=backend: single.awac(r, c, v, g.n, st, backend=b),
            iters=3, warmup=1,
        )
        iters = int(iters)
        w = float(single.matching_weight(stf, g.n))
        us[backend] = dt / max(iters, 1) * 1e6
        weights[backend] = w
        if backend.startswith("pallas"):
            interp[backend] = resolve_execution(None).interpret
        if emit_rows:
            row(f"awac_iter_{backend}_n{n}", us[backend],
                f"iters={iters};iters_per_s={iters / dt:.1f};weight={w:.4f}"
                + _mode_note(backend))
    return us, interp, weights


def bench_awac_sweep(n: int = 2048, avg_degree: float = 8.0):
    """End-to-end AWAC on a synthetic n x n instance: seed reference path vs
    the fused engines (CSR-windowed lookup + packed-key Step C; per-sweep
    and persistent whole-loop Pallas kernels). All backends run the
    identical select/augment semantics and must converge to the same
    matching weight; reports per-iteration time and iterations/sec, with
    Pallas rows annotated ``interpret=`` (interpreter timings are
    correctness-grade, never kernel timings). Returns (xla speedup vs
    reference, per-backend us, per-pallas-backend interpret flags)."""
    us, interp, weights = _measure_awac_single(n, avg_degree, emit_rows=True)
    speedup = us["reference"] / us["xla"]
    row(f"awac_fused_speedup_n{n}", us["xla"],
        f"speedup_vs_reference={speedup:.2f}x;"
        f"weight_identical="
        f"{abs(weights['reference'] - weights['xla']) == 0.0}")
    if "pallas_persistent" in us and "pallas" in us:
        same = weights["reference"] == weights["pallas_persistent"]
        row(f"awac_persistent_speedup_n{n}", us["pallas_persistent"],
            f"speedup_vs_pallas_sweep="
            f"{us['pallas'] / us['pallas_persistent']:.2f}x;"
            f"weight_identical={same}" + _mode_note("pallas_persistent"))
    return speedup, us, interp


def _measure_awac_batched(n: int, bsize: int, avg_degree: float = 6.0):
    """Per-iteration AWAC time for every local backend on a stacked batch
    (shared greedy+MCM state prep; only the AWAC phase is timed)."""
    from repro.core import MatchingProblem, batch, graph

    kinds = ("uniform", "circuit", "banded", "powerlaw", "antigreedy")
    gs = [graph.generate(n, avg_degree=avg_degree, kind=kinds[i % len(kinds)],
                         seed=i) for i in range(bsize)]
    p = MatchingProblem.stack(gs)
    r, c, v = p.row, p.col, p.val
    ws = batch._resolve_window_steps_batched(r, n, None)
    rp = batch.batched_row_ptr_from_sorted(r, n)
    mr, mc = batch.greedy_maximal_batched(r, c, v, n)
    mr, mc = batch.mcm_batched(r, c, v, n, mr, mc)
    st = batch._state_from_mates_windowed(r, c, v, rp, n, mr, mc, ws)

    us, interp = {}, {}
    for backend in _backends():
        dt, (stf, iters) = time_call(
            lambda b=backend: batch.awac_batched(
                r, c, v, n, st, backend=b, row_ptr=rp, window_steps=ws),
            iters=3, warmup=1,
        )
        mean_iters = float(np.mean(np.asarray(iters)))
        us[backend] = dt / max(mean_iters, 1.0) * 1e6
        if backend.startswith("pallas"):
            interp[backend] = resolve_execution(None).interpret
    return us, interp


def bench_dispatch(single_large=None):
    """Measure every (shape class x backend) cell and persist the winners
    as the dispatch table (``BENCH_dispatch.json``) that
    ``backend="auto"`` consults (``repro.kernels.dispatch``). Reuses the
    ``bench_awac_sweep`` measurements for the large single class when
    provided. Emits one summary row per class."""
    platform = jax.default_backend()
    cells = {}
    if single_large is not None:
        cells["single_large"] = single_large
    else:
        cells["single_large"] = _measure_awac_single(2048, 8.0)[:2]
    cells["single_small"] = _measure_awac_single(96, 6.0)[:2]
    cells["batched_small"] = _measure_awac_batched(24, 8)
    cells["batched_large"] = _measure_awac_batched(512, 4)

    entries = {}
    for klass, (us, interp) in cells.items():
        winner = min(us, key=us.get)
        entries[f"{platform}/{klass}"] = {
            "winner": winner,
            "us_per_iter": {b: round(t, 1) for b, t in us.items()},
            "interpret": interp,
        }
        ranked = sorted(us, key=us.get)
        row(f"dispatch_{klass}", us[winner],
            f"winner={winner};runner_up={ranked[1]};"
            f"margin={us[ranked[1]] / us[winner]:.2f}x;platform={platform}")
    kdispatch.save_table(entries, {
        "date": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "jax": jax.__version__,
        "backend": platform,
        "measured_backends": list(_backends()),
    })
    return entries


def bench_awpm_batched(n: int = 24, avg_degree: float = 6.0,
                       batch_sizes=(1, 8, 32)):
    """Aggregate matching throughput: one batched ``api.solve`` dispatch for
    B instances vs a python loop of per-instance single-problem ``solve``
    calls (the pre-batching serving pattern). Sized for the
    many-small-instances regime the engine targets (MoE routing blocks,
    per-block pivot preprocessing) — at large n the per-instance compute
    dominates CPU dispatch and the lockstep batch loses its edge
    (DESIGN.md §4). Reports matchings/sec for both and the aggregate
    speedup at each B."""
    from repro.core import MatchingProblem, graph, solve

    b_max = max(batch_sizes)
    kinds = ("uniform", "circuit", "banded", "powerlaw", "antigreedy")
    gs = [graph.generate(n, avg_degree=avg_degree, kind=kinds[i % len(kinds)],
                         seed=i) for i in range(b_max)]
    stacked = MatchingProblem.stack(gs)
    row_all, col_all, val_all = stacked.row, stacked.col, stacked.val

    speedups = {}
    for b in batch_sizes:
        rows, cols, vals = row_all[:b], col_all[:b], val_all[:b]
        pb = MatchingProblem(row=rows, col=cols, val=vals, n=n)
        ps = [MatchingProblem(row=rows[i], col=cols[i], val=vals[i], n=n)
              for i in range(b)]
        dt_b, resB = time_call(lambda: solve(pb), iters=3, warmup=1)
        dt_l, outs = time_call(
            lambda: [solve(ps[i]) for i in range(b)], iters=3, warmup=1)
        wB = np.array(resB.weight)
        wL = np.array([float(r.weight) for r in outs])
        identical = bool((wB == wL).all())
        speedups[b] = dt_l / dt_b
        row(f"awpm_batched_B{b}_n{n}", dt_b / b * 1e6,
            f"matchings_per_s={b / dt_b:.1f};"
            f"loop_matchings_per_s={b / dt_l:.1f};"
            f"aggregate_speedup={dt_l / dt_b:.2f}x;"
            f"weights_identical={identical}")
    return speedups


def run():
    _, us_large, interp_large = bench_awac_sweep()
    bench_dispatch(single_large=(us_large, interp_large))
    bench_awpm_batched()
    rng = np.random.default_rng(0)
    # cycle_gain: M=N=512 dense tile
    m = n = 512
    a = jnp.asarray(rng.uniform(0.1, 1, (m, n)) * (rng.random((m, n)) < 0.3),
                    jnp.float32)
    a2 = jnp.asarray(rng.uniform(0.1, 1, (m, n)) * (rng.random((m, n)) < 0.3),
                     jnp.float32)
    u = jnp.asarray(rng.uniform(0, 1, m), jnp.float32)
    v = jnp.asarray(rng.uniform(0, 1, n), jnp.float32)
    dt_ref, _ = time_call(lambda: cycle_gain_ref(a, a2, u, v), iters=5)
    ai = (3 * m * n) / (2 * 4 * m * n)  # flops per byte (2 arrays in, f32)
    row("cycle_gain_ref_512", dt_ref * 1e6,
        f"arith_intensity={ai:.2f}flop/B;v5e_bound=memory")
    dt_k, _ = time_call(
        lambda: cycle_gain_padded(a, a2, u, v, tm=256, tn=256), iters=2)
    row("cycle_gain_pallas_interp_512", dt_k * 1e6,
        resolve_execution(None).describe())

    # flash attention S=512 D=64
    q = jnp.asarray(rng.normal(size=(1, 4, 512, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 512, 64)), jnp.bfloat16)
    vv = jnp.asarray(rng.normal(size=(1, 2, 512, 64)), jnp.bfloat16)
    dt_ref, _ = time_call(lambda: attention_ref(q, k, vv), iters=3)
    flops = 4 * 1 * 4 * 512 * 512 * 64
    row("attention_ref_512", dt_ref * 1e6,
        f"flops={flops:.2e};v5e_us={flops / 197e12 * 1e6:.2f}")
    dt_k, _ = time_call(lambda: flash_attention(q, k, vv), iters=1)
    row("flash_attention_interp_512", dt_k * 1e6,
        resolve_execution(None).describe())

    # router_swap: T=512 tokens, E=64 experts
    from repro.kernels.router_swap import router_swap_padded, router_swap_ref

    aff = jnp.asarray(rng.normal(size=(512, 64)), jnp.float32)
    assign = jnp.asarray(rng.integers(0, 64, 512), jnp.int32)
    cur = jnp.take_along_axis(aff, assign[:, None], axis=1)[:, 0]
    dt_ref, _ = time_call(lambda: router_swap_ref(aff, assign, cur), iters=3)
    row("router_swap_ref_512", dt_ref * 1e6, "materializes [T,T]")
    dt_k, _ = time_call(
        lambda: router_swap_padded(aff, assign, cur, ti=256, tj=256), iters=2)
    row("router_swap_pallas_interp_512", dt_k * 1e6,
        "tiled, no [T,T] in HBM;" + resolve_execution(None).describe())

    # embedding bag B=64 L=16 V=4096 D=64
    idx = jnp.asarray(rng.integers(-1, 4096, (64, 16)), jnp.int32)
    w = jnp.asarray(rng.uniform(0, 1, (64, 16)), jnp.float32)
    tbl = jnp.asarray(rng.normal(size=(4096, 64)), jnp.float32)
    dt_ref, _ = time_call(lambda: embedding_bag_ref(idx, w, tbl), iters=5)
    row("embedding_bag_ref", dt_ref * 1e6, "take+segsum")
    dt_k, _ = time_call(
        lambda: embedding_bag_padded(idx, w, tbl, tb=8, tv=512), iters=2)
    row("embedding_bag_pallas_interp", dt_k * 1e6,
        resolve_execution(None).describe())
    return True


if __name__ == "__main__":
    run()
