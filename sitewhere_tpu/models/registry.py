"""Model registry: name → (config class, model class).

The tenant config's `rule-processing` section selects a model by name
(the way the reference's tenant config selects Groovy scripts / Siddhi
queries per tenant, [SURVEY.md §5.6]).
"""

from __future__ import annotations

from typing import Any

from sitewhere_tpu.models.dsv3 import Dsv3Config, Dsv3StreamModel
from sitewhere_tpu.models.laguna import LagunaConfig, LagunaStreamModel
from sitewhere_tpu.models.lfm2 import Lfm2Config, Lfm2StreamModel
from sitewhere_tpu.models.longwin import LongWindowConfig, LongWindowModel
from sitewhere_tpu.models.lstm import (
    LstmAnomalyModel,
    LstmConfig,
    StreamingLstmModel,
)
from sitewhere_tpu.models.nemotron_h import (
    NemotronHConfig,
    NemotronHStreamModel,
)
from sitewhere_tpu.models.olmo_hybrid import (
    OlmoHybridConfig,
    OlmoHybridStreamModel,
)
from sitewhere_tpu.models.ouro import OuroConfig, OuroStreamModel
from sitewhere_tpu.models.seasonal import (
    SeasonalTrendConfig,
    SeasonalTrendForecaster,
)
from sitewhere_tpu.models.tft import TftConfig, TftForecaster
from sitewhere_tpu.models.zscore import ZScoreConfig, ZScoreModel

MODEL_REGISTRY: dict[str, tuple[type, type]] = {
    "lstm": (LstmConfig, LstmAnomalyModel),
    "lstm-stream": (LstmConfig, StreamingLstmModel),
    # DeepSeek-V3's block (MLA, routed experts) as a streaming scorer
    "dsv3-stream": (Dsv3Config, Dsv3StreamModel),
    # Laguna-S-2.1's block (windowed and full GQA side by side, gated
    # heads, softmax-routed experts) as a streaming scorer
    "laguna-stream": (LagunaConfig, LagunaStreamModel),
    # Olmo-Hybrid-7B's block (gated delta-rule layers with a matrix
    # state a head, a full-attention layer a period) as a streaming scorer
    "olmo-hybrid-stream": (OlmoHybridConfig, OlmoHybridStreamModel),
    # LFM2-24B-A2B's block (gated short convolutions three to one with
    # grouped-query attention, two dense layers, then sigmoid-routed
    # experts with none shared) as a streaming scorer
    "lfm2-stream": (Lfm2Config, Lfm2StreamModel),
    # Ouro-2.6B's looped stack (the same layers run several passes an
    # event, each pass over its own key-value contexts) as a streaming
    # scorer
    "ouro-stream": (OuroConfig, OuroStreamModel),
    # NVIDIA-Nemotron-3-Super-120B-A12B's block (Mamba-2 layers with a
    # matrix state a head, latent experts of relu squared with one shared
    # expert, grouped-query attention, each layer one of the three) as a
    # streaming scorer
    "nemotron-h-stream": (NemotronHConfig, NemotronHStreamModel),
    "tft": (TftConfig, TftForecaster),
    "zscore": (ZScoreConfig, ZScoreModel),
    "longwin": (LongWindowConfig, LongWindowModel),
    # the fleet's own load forecaster (fleet/forecast.py tenant-0)
    "seasonal": (SeasonalTrendConfig, SeasonalTrendForecaster),
}


def register_model(name: str, cfg_cls: type, model_cls: type) -> None:
    MODEL_REGISTRY[name] = (cfg_cls, model_cls)


def build_model(name: str, **cfg_overrides: Any):
    """Instantiate a model by registry name with config overrides."""
    try:
        cfg_cls, model_cls = MODEL_REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown model {name!r} (known: {sorted(MODEL_REGISTRY)})") from None
    return model_cls(cfg_cls(**cfg_overrides))
