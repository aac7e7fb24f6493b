"""Model zoo beyond vision (flagship NLP models)."""
from .ernie import (  # noqa: F401
    ErnieForMaskedLM,
    ErnieForSequenceClassification,
    ErnieModel,
    ernie_3_0_base,
    ernie_3_0_medium,
    ernie_tiny,
)
from .llama import LlamaForCausalLM, LlamaModel, llama_tiny  # noqa: F401
from .nemotron_h import NemotronHForCausalLM, NemotronHModel  # noqa: F401
from .deepseek_v32 import DeepseekV32ForCausalLM, DeepseekV32Model  # noqa: F401
from .pangu_ultra_moe import PanguUltraMoEForCausalLM, PanguUltraMoEModel  # noqa: F401
from .ocr import CRNN, DBNet, OCRSystem, ctc_greedy_decode, db_loss, db_postprocess  # noqa: F401
from .detection import PPYOLOE, ppyoloe_loss  # noqa: F401
