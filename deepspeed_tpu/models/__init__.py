"""deepspeed_tpu.models — model families built on the fused ops layer.

The reference ships models through DeepSpeedExamples (Megatron-GPT2,
bing_bert) and fuses them via module injection; here the flagship
transformer-LM families are first-class so the framework is usable
standalone.
"""

from .gpt2 import GPT2Config, GPT2Model
from .bert import BertConfig, BertModel
from .gpt_moe import GPTMoEConfig, GPTMoEModel
from .phi4flash import Phi4FlashConfig, Phi4FlashModel
from .keye_vl2 import KeyeVL2Config, KeyeVL2Model
from .granite_hybrid import GraniteHybridConfig, GraniteHybridModel
from .xing4 import Xing4Config, Xing4Model
from .nemotron_h import NemotronHConfig, NemotronHModel
from .zaya import ZayaConfig, ZayaModel

__all__ = ["GPT2Config", "GPT2Model", "BertConfig", "BertModel",
           "GPTMoEConfig", "GPTMoEModel", "Phi4FlashConfig",
           "Phi4FlashModel", "KeyeVL2Config", "KeyeVL2Model",
           "GraniteHybridConfig", "GraniteHybridModel", "Xing4Config",
           "Xing4Model", "NemotronHConfig", "NemotronHModel", "ZayaConfig", "ZayaModel"]
