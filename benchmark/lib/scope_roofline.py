"""The reader behind the ``<scope>_roofline`` metrics of the train step:
needed FLOPs of one chip and step over the chip's peak, divided by the
device time under the scope per step."""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from benchmark.lib import peaks, scope_flops, scopes


def percent(obs: Dict[str, Any], part: str, under: Tuple[str, ...]
            ) -> Optional[float]:
    t = obs.get("train")
    if not t or not t["traced_steps"]:
        return None
    busy = scopes.model_scope_seconds(obs, under)
    if not busy:
        return None
    need = scope_flops.train_flops(obs["model"], part,
                                   t["tokens_per_step"] / t["chips"])
    floor_s = need / peaks.peaks(obs["device"]["device_kind"])["bf16_flops"]
    return 100.0 * floor_s / (busy / t["traced_steps"])
