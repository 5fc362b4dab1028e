"""Measured backend dispatch table for ``backend="auto"``.

The seed resolved ``"auto"`` with a hard-coded platform rule ("pallas on
TPU, xla elsewhere") — an asserted claim, not a measured one, and on CPU it
was measurably wrong once the Pallas interpreter numbers were labeled
honestly. This module replaces the rule with a small measured table,
persisted as ``BENCH_dispatch.json`` next to ``BENCH_kernels.json`` at the
repo root and refreshed by the kernels bench job (``benchmarks/
bench_kernels.py``), which times every local AWAC backend per shape class
and records the winner.

Table schema (one entry per ``<platform>/<shape class>``)::

    {"entries": {"cpu/single_large": {
         "winner": "xla",
         "us_per_iter": {"reference": 5276.2, "xla": 2525.4, ...},
         "interpret": {"pallas": true, "pallas_persistent": true}},
      ...},
     "metadata": {...}}

Shape classes are deliberately coarse — ``{single|batched}_{small|large}``
with the small/large split at ``n <= SMALL_N`` — because the bench job must
be able to measure every class on every CI run. Lookup falls back
class -> same-kind class -> any class for the platform -> None; a None
answer means "unmeasured here", and the caller (``core.single.
resolve_backend``) falls back to the fused XLA sweep, labeled "heuristic".
A winner that cannot compile on the platform (a Pallas sweep on TPU) is
passed over the same way.

``check_regression.py --dispatch`` gates the committed table against fresh
measurements so a stale winner (losing by more than the routing factor)
fails CI instead of silently mis-routing ``auto``.
"""
from __future__ import annotations

import json
import os
import pathlib
from typing import Any

#: committed location: repo root, next to BENCH_kernels.json
DEFAULT_TABLE_PATH = pathlib.Path(__file__).resolve().parents[3] \
    / "BENCH_dispatch.json"

#: env override for tests / alternate deployments
TABLE_ENV_VAR = "REPRO_DISPATCH_TABLE"

#: boundary of the {small, large} shape-class split (inclusive small side)
SMALL_N = 256

#: backends the bench job measures per class (order = bench order)
MEASURED_BACKENDS = ("reference", "xla", "pallas", "pallas_persistent")

_CACHE: dict[str, dict | None] = {}


def table_path() -> pathlib.Path:
    return pathlib.Path(os.environ.get(TABLE_ENV_VAR, DEFAULT_TABLE_PATH))


def shape_class(n: int | None, batch: int | None = None) -> str:
    """Coarse shape class: ``{single|batched}_{small|large}``.

    ``n=None`` (shape unknown at resolve time, e.g. the resilient runtime
    resolving a backend name without a problem in hand) conservatively maps
    to the large single-instance class — the class whose winner is the
    safest default for arbitrary work.
    """
    kind = "batched" if batch is not None and batch > 1 else "single"
    size = "large" if n is None or n > SMALL_N else "small"
    return f"{kind}_{size}"


def load_table(path: str | os.PathLike | None = None) -> dict | None:
    """Load (and cache) the dispatch table; None when absent/unreadable."""
    p = str(path if path is not None else table_path())
    if p in _CACHE:
        return _CACHE[p]
    try:
        with open(p) as f:
            table = json.load(f)
        if not isinstance(table.get("entries"), dict):
            table = None
    except (OSError, ValueError):
        table = None
    _CACHE[p] = table
    return table


def clear_cache() -> None:
    _CACHE.clear()


def _entry(table: dict, platform: str, klass: str) -> dict | None:
    entries = table["entries"]
    hit = entries.get(f"{platform}/{klass}")
    if hit is not None:
        return hit
    # same kind (single/batched), other size
    kind = klass.split("_")[0]
    for key, e in sorted(entries.items()):
        plat, _, kl = key.partition("/")
        if plat == platform and kl.startswith(kind):
            return e
    # any class measured on this platform
    for key, e in sorted(entries.items()):
        if key.partition("/")[0] == platform:
            return e
    return None


def choose_backend(n: int | None = None, batch: int | None = None,
                   platform: str | None = None,
                   path: str | os.PathLike | None = None) -> str | None:
    """Measured winner for (platform, shape class), or None if unmeasured.

    None tells the caller to fall back to its heuristic — the table never
    guesses about platforms it has no measurements for.
    """
    table = load_table(path)
    if table is None:
        return None
    if platform is None:
        import jax

        platform = jax.default_backend()
    entry = _entry(table, platform, shape_class(n, batch))
    if entry is None:
        return None
    winner = entry.get("winner")
    return winner if isinstance(winner, str) and winner else None


def save_table(entries: dict[str, Any], metadata: dict[str, Any],
               path: str | os.PathLike | None = None) -> pathlib.Path:
    """Persist a freshly measured table (bench job) and drop the cache."""
    p = pathlib.Path(path if path is not None else table_path())
    with open(p, "w") as f:
        json.dump({"entries": entries, "metadata": metadata}, f, indent=1)
        f.write("\n")
    clear_cache()
    return p
