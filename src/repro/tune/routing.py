"""Routing layer: table lookups with shipped defaults.

This module owns the *shipped defaults* that used to live as hard-coded
constants in ``kernels/ops.py`` (``DECODE_M_MAX = 16``,
``_SPMM_BLOCK_ELEMS = 1 << 22``) and the Pallas GEMV tile shape, and
answers every routing question the kernels ask:

* :func:`decode_m_max` — the gemv/spmm crossover width ``nmg_matmul`` /
  ``nmg_linear`` route on,
* :func:`spmm_block_elems` — the gathered-operand cap of one XLA spmm
  block,
* :func:`gemv_pallas_config` — the Pallas gemv activation-row tile /
  decompression window depth,
* :func:`spmm_pallas_config` — the Pallas spmm token tile / window depth
  and whether the streamed schedule (activation slab resident, windows
  looped in the kernel) is used,
* :func:`fused_qkv` / :func:`fused_ffn` — whether the decode megakernels
  (``kernels/nmg_fused.py``) fuse eligible projection groups into one
  launch or fall back to per-projection gemv,
* :func:`conversion_cost` — measured lossless-conversion costs the
  dispatcher's tie-breaker consults (``core/dispatch.py``).

Each lookup returns ``(value, source)`` where ``source`` is ``"table"``
for a hit in the active :class:`~repro.tune.table.TuningTable` and
``"default"`` otherwise, so callers can surface the provenance in their
counters.  With no active table every answer is exactly the old
hard-coded behavior — loading a table is strictly opt-in.

Lookups happen at **trace time** (the kernels read them while JAX traces
a jitted caller), so a table must be active *before* the consuming
program compiles; swapping tables does not retrace already-compiled
programs.  The serving warmup hook (``serve/engine.py:warmup_engine``)
exists precisely to tune-then-compile in the right order.
"""

from __future__ import annotations

import collections
import os
import sys
import warnings
from typing import Optional

from repro.tune.table import TuningTable, bucket, shape_key

__all__ = [
    "DEFAULT_DECODE_M_MAX",
    "DEFAULT_SPMM_BLOCK_ELEMS",
    "DEFAULT_GEMV_PALLAS",
    "DEFAULT_SPMM_PALLAS",
    "DEFAULT_FUSED_QKV",
    "DEFAULT_FUSED_FFN",
    "ENV_TABLE",
    "active_table",
    "set_active_table",
    "clear_active_table",
    "load_table",
    "load_table_cli",
    "table_load_events",
    "decode_m_max",
    "spmm_block_elems",
    "gemv_pallas_config",
    "spmm_pallas_config",
    "fused_qkv",
    "fused_ffn",
    "conversion_cost",
    "matmul_latency_us",
]

#: widest right operand still considered decode-shaped when no table is
#: active (slot batches are single-token, so M == number of serving slots)
DEFAULT_DECODE_M_MAX = 16

#: default cap on the gathered-operand size (elements) of one XLA spmm
#: block — bounds peak memory like the old per-group scan did
DEFAULT_SPMM_BLOCK_ELEMS = 1 << 22

#: default Pallas gemv tile config: up to 128 activation rows per grid
#: step, ~128 compressed positions per decompression window
DEFAULT_GEMV_PALLAS = {"tm": 128, "target_depth": 128}

#: default Pallas spmm config: 1024 tokens per grid step, ~256 compressed
#: positions per window, on the grid schedule.  Each token tile decompresses
#: the whole weight on the MXU again, but that is the smaller cost: a fit of
#: T = a * tiles + b to StarCoder2-15B's c_fc at 2048 tokens (20.4 ms on 16
#: tiles, 10.1 on 2) gives a ~0.74 ms a pass over the weight and b ~8.6 ms
#: that scales with the tokens, so decompression is ~1.5 of the 10.1 ms at
#: 1024-token tiles.  The grid schedule holds one window of activations,
#: not the ``[tile, K]`` slab the streamed one keeps resident (at
#: K = 24576 that slab overran the kernel's scoped VMEM on a TPU v5e).
#: Timed on a v5e, before the kernel's 128-lane tiles
#: (1:4:16, gr=64, bf16) against the earlier default, 128-token tiles on
#: the streamed schedule: StarCoder2-15B's FFN (6144 x 24576 and
#: 24576 x 6144) at 2048 tokens 10.1 and 9.0 ms against 20.4 and 29.8
#: (128-token tiles on the grid, the slab not fitting); bert-base's
#: (768 x 3072 and 3072 x 768) within 0.03 ms either way at 32-256 tokens,
#: and 0.75 and 0.73 ms against 1.13 and 0.88 at 2048
DEFAULT_SPMM_PALLAS = {"tn": 1024, "target_depth": 256, "stream": False}

#: decode megakernels fuse by default — eligibility (matching formats,
#: decode-shaped M) is the kernels' business; the table can veto per bucket
DEFAULT_FUSED_QKV = True
DEFAULT_FUSED_FFN = True

#: environment variable naming a table file to auto-load (opt-in; read by
#: :func:`load_table_cli`, which the CLI entry points call)
ENV_TABLE = "REPRO_TUNE_TABLE"

_ACTIVE: Optional[TuningTable] = None


def active_table() -> Optional[TuningTable]:
    return _ACTIVE


def set_active_table(table: Optional[TuningTable]) -> None:
    """Install ``table`` as the process-wide routing source (None restores
    the shipped defaults).  Also wires the dispatcher's conversion-cost
    tie-breaker to the table's measured costs (and unwires it on None)."""
    global _ACTIVE
    _ACTIVE = table
    import importlib

    # module object import: the core package re-exports a *function* named
    # ``dispatch``, shadowing the submodule on attribute-style imports
    disp = importlib.import_module("repro.core.dispatch")
    disp.set_conversion_cost_model(
        conversion_cost if table is not None else None
    )


def clear_active_table() -> None:
    set_active_table(None)


# table-load provenance: ("table", "loaded" | "load_failed") -> count.
# Deliberately *not* reset with the routing counters — a corrupt table that
# was ever swallowed in this process stays visible to the checker and to
# post-mortem debugging even after the run fell back to defaults.
_LOAD_EVENTS: collections.Counter = collections.Counter()


def table_load_events() -> dict:
    """{("table", "loaded" | "load_failed"): count} for this process."""
    return dict(_LOAD_EVENTS)


def load_table(path: str) -> Optional[TuningTable]:
    """Load ``path``'s section for the running device and make it active.

    A corrupt, truncated, or schema-mismatched file is *not* fatal: it
    warns (``RuntimeWarning``), records a ``("table", "load_failed")``
    provenance event, leaves whatever table was previously active
    untouched, and returns None — the run proceeds on shipped defaults
    rather than dying because an optional optimization artifact rotted."""
    try:
        table = TuningTable.load(path)
    except (OSError, ValueError) as e:
        _LOAD_EVENTS[("table", "load_failed")] += 1
        warnings.warn(
            f"tuning table {path!r} failed to load ({e}) — routing falls "
            f"back to shipped defaults",
            RuntimeWarning,
            stacklevel=2,
        )
        return None
    _LOAD_EVENTS[("table", "loaded")] += 1
    set_active_table(table)
    return table


def load_table_cli(path: Optional[str], *, verbose: bool = True
                   ) -> Optional[TuningTable]:
    """The CLI entry points' one-stop loader: an explicit ``path`` wins,
    otherwise ``$REPRO_TUNE_TABLE`` is honored; either way the loaded
    table is announced — and a dangling env path is warned about —
    because tuning silently not taking effect is the failure mode this
    message exists to surface.  Returns None when neither source names a
    (readable) table."""
    if path:
        # the user explicitly asked for this table: a load failure is an
        # error, not a fall-back (silently running untuned would defeat
        # the point of passing --tuning-table)
        table = load_table(path)
        if table is None:
            raise ValueError(
                f"tuning table {path!r} failed to load (see warning above)"
            )
        src = path
    else:
        env = os.environ.get(ENV_TABLE)
        if not env:
            return None
        # the env spelling must not crash unrelated commands, but going
        # quiet would leave the user believing the run was tuned — so warn
        # on a missing, stale-schema, or corrupt env table and fall back
        # to defaults
        if not os.path.exists(env):
            print(f"tuning: ${ENV_TABLE}={env} does not exist — "
                  f"using shipped defaults", file=sys.stderr)
            return None
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            table = load_table(env)
        if table is None:
            msg = str(caught[-1].message) if caught else "load failed"
            print(f"tuning: ${ENV_TABLE}={env} is unreadable ({msg}) — "
                  f"using shipped defaults", file=sys.stderr)
            return None
        src = f"${ENV_TABLE}={env}"
    if verbose:
        print(f"tuning: loaded {len(table)} entries for {table.device} "
              f"from {src}")
    return table


# ---------------------------------------------------------------------------
# lookups
# ---------------------------------------------------------------------------


def _lookup(key: str, default):
    if _ACTIVE is not None:
        hit = _ACTIVE.get(key)
        if hit is not None:
            return hit, "table"
    return default, "default"


def decode_m_max(*, K: int, R: int, fmt: tuple, gr: int, dtype
                 ) -> tuple[int, str]:
    """Widest right operand routed to the GEMV path for this shape bucket.
    Exact-bucket hit, else the device-wide ``decode_m_max`` override, else
    the shipped default."""
    val, src = _lookup(
        shape_key("decode_m_max", K=K, R=R, fmt=fmt, gr=gr, dtype=dtype),
        None,
    )
    if val is None:
        val, src = _lookup("decode_m_max", DEFAULT_DECODE_M_MAX)
    return int(val), src


def spmm_block_elems() -> tuple[int, str]:
    """Gathered-operand element cap per XLA spmm block (device-wide: the
    cap protects peak memory, which does not depend on the shape bucket
    or dtype)."""
    val, src = _lookup("spmm_block_elems", DEFAULT_SPMM_BLOCK_ELEMS)
    return int(val), src


def gemv_pallas_config(*, K: int, R: int, fmt: tuple, gr: int, dtype
                       ) -> tuple[dict, str]:
    """Pallas gemv tile config {tm, target_depth} for this shape bucket."""
    val, src = _lookup(
        shape_key("gemv_pallas", K=K, R=R, fmt=fmt, gr=gr, dtype=dtype),
        None,
    )
    if val is None:
        val, src = _lookup("gemv_pallas", DEFAULT_GEMV_PALLAS)
    cfg = dict(DEFAULT_GEMV_PALLAS)
    cfg.update(val)
    return cfg, src


def spmm_pallas_config(*, K: int, R: int, fmt: tuple, gr: int, dtype
                       ) -> tuple[dict, str]:
    """Pallas spmm config {tn, target_depth, stream} for this shape bucket.
    Exact-bucket hit, else the device-wide ``spmm_pallas`` override, else
    the shipped default (windows on the grid)."""
    val, src = _lookup(
        shape_key("spmm_pallas", K=K, R=R, fmt=fmt, gr=gr, dtype=dtype),
        None,
    )
    if val is None:
        val, src = _lookup("spmm_pallas", DEFAULT_SPMM_PALLAS)
    cfg = dict(DEFAULT_SPMM_PALLAS)
    cfg.update(val)
    return cfg, src


def fused_qkv(*, K: int, R: int, fmt: tuple, gr: int, dtype
              ) -> tuple[bool, str]:
    """Whether eligible attention projections fuse into the single-launch
    QKV megakernel for this shape bucket (``R`` is the *summed* output
    rows of the fused group).  Bucket hit, else device-wide, else True."""
    val, src = _lookup(
        shape_key("fused_qkv", K=K, R=R, fmt=fmt, gr=gr, dtype=dtype),
        None,
    )
    if val is None:
        val, src = _lookup("fused_qkv", DEFAULT_FUSED_QKV)
    return bool(val), src


def fused_ffn(*, K: int, R: int, fmt: tuple, gr: int, dtype
              ) -> tuple[bool, str]:
    """Whether an eligible packed gated-MLP weight routes to the fused
    projection+gate megakernel for this shape bucket."""
    val, src = _lookup(
        shape_key("fused_ffn", K=K, R=R, fmt=fmt, gr=gr, dtype=dtype),
        None,
    )
    if val is None:
        val, src = _lookup("fused_ffn", DEFAULT_FUSED_FFN)
    return bool(val), src


def matmul_latency_us(*, K: int, R: int, fmt: tuple, gr: int, dtype,
                      M: int) -> tuple[Optional[float], str]:
    """Measured best-path latency (us) of one routed sparse matmul at
    right-operand width ``M`` for this shape bucket, or None when the
    active table has no measurement (there is no meaningful shipped
    default for an absolute latency — callers fall back to online
    observation).  Recorded by ``tune_decode_threshold`` from the same
    gemv/spmm sweep that sets the bucket's crossover; the serving SLO
    controller's admission-time cost prediction
    (``serve/slo.py:LatencyModel``) is the consumer."""
    key = (shape_key("matmul_latency", K=K, R=R, fmt=fmt, gr=gr,
                     dtype=dtype) + f"/M{bucket(M)}")
    val, src = _lookup(key, None)
    return (None if val is None else float(val)), src


def conversion_cost(src_cls: type, dst_cls: type) -> Optional[float]:
    """Measured cost (us) of a lossless ``src -> dst`` conversion, or None
    when the active table has no measurement.  ``core/dispatch.py`` uses
    this to break ties among conversion candidates that need the same
    *number* of conversions; with no table (or no measurement) the
    dispatcher keeps its registration-order tie-break, so default behavior
    is unchanged."""
    if _ACTIVE is None or src_cls is dst_cls:
        return None
    return _ACTIVE.get(f"convert_cost/{src_cls.__name__}->{dst_cls.__name__}")
