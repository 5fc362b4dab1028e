"""Kernel micro-benchmarks: Pallas (interpret mode — correctness-grade
timings on CPU; the TPU perf story lives in the roofline analysis) vs jnp
reference, plus arithmetic-intensity derivations for the v5e roofline, plus
the end-to-end AWAC iterations/sec contest between the seed implementation
and the fused sparse sweep engine (DESIGN.md §3)."""
import jax.numpy as jnp
import numpy as np

from benchmarks._util import row, time_call
from repro.kernels.backend import resolve_execution
from repro.kernels.cycle_gain import cycle_gain_padded, cycle_gain_ref
from repro.kernels.embedding_bag import embedding_bag_padded, embedding_bag_ref
from repro.kernels.flash_attention import attention_ref, flash_attention


def bench_awac(n: int = 2048, avg_degree: float = 8.0):
    """End-to-end AWAC on a synthetic n x n instance: the seed reference
    path vs the fused XLA sweep (CSR-windowed lookup + packed-key Step C).
    Both run the identical select/augment semantics and must converge to
    the same matching weight; reports per-iteration time and
    iterations/sec. Returns the fused sweep's speedup over reference."""
    from repro.core import graph, single

    g = graph.generate(n, avg_degree=avg_degree, kind="uniform", seed=0)
    r, c, v = jnp.asarray(g.row), jnp.asarray(g.col), jnp.asarray(g.val)
    st = single.greedy_maximal(r, c, v, g.n)
    st = single.mcm(r, c, v, g.n, st.mate_row, st.mate_col)

    us, weights = {}, {}
    for backend in ("reference", "xla"):
        dt, (stf, iters) = time_call(
            lambda b=backend: single.awac(r, c, v, g.n, st, backend=b),
            iters=3, warmup=1,
        )
        iters = int(iters)
        weights[backend] = float(single.matching_weight(stf, g.n))
        us[backend] = dt / max(iters, 1) * 1e6
        row(f"awac_iter_{backend}_n{n}", us[backend],
            f"iters={iters};iters_per_s={iters / dt:.1f};"
            f"weight={weights[backend]:.4f}")
    speedup = us["reference"] / us["xla"]
    row(f"awac_fused_speedup_n{n}", us["xla"],
        f"speedup_vs_reference={speedup:.2f}x;"
        f"weight_identical="
        f"{abs(weights['reference'] - weights['xla']) == 0.0}")
    return speedup


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
    bench_awac()
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
