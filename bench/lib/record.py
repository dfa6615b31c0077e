"""What one run leaves for the metric readers, and the model it builds."""

from __future__ import annotations

import dataclasses
from typing import Optional

from bench.lib.weights import model_dims


@dataclasses.dataclass
class Run:
    """One run of one cell.

    Times are host seconds on one clock (``time.perf_counter`` based);
    ``window`` is the measured window's (start, end).  A serving run has
    ``requests`` (due, admitted, delivered times and tokens per request).
    ``trace`` is the reduced device trace of a ``--trace 1`` run.
    ``control_correct`` is the control's verdict by the same rule, in a run
    that read the control (``bench/control.py``), else None.
    """

    kind: str
    workload: dict
    config: dict
    seed: int
    seconds: float
    setup_s: float
    window: tuple
    device: dict
    correct: bool
    checks: dict
    attempted: int
    failed: int
    requests: list = dataclasses.field(default_factory=list)
    control_correct: Optional[bool] = None
    trace: Optional[object] = None
    peaks: Optional[dict] = None


def model_config(cfg: dict):
    """The system's ``ModelConfig`` for a configuration file."""
    from repro.models.common import ModelConfig

    d = model_dims(cfg)
    if cfg["hidden_act"] != "gelu_tanh":
        raise ValueError(f"unsupported activation {cfg['hidden_act']!r}")
    return ModelConfig(
        name=cfg["name"], vocab=d["V"], d_model=d["D"], n_layers=d["L"],
        n_heads=d["H"], n_kv_heads=d["KV"], head_dim=d["hd"], d_ff=d["F"],
        attn_type="gqa", act="gelu", gated_mlp=False,
        rope_theta=float(cfg["rope_theta"]), dtype=cfg["torch_dtype"])
