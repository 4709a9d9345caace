import time as _time

_import_t0 = _time.perf_counter()  # the package's ``import`` record starts here

from deepspeed_tpu.models.gpt2 import (GPT2Config, GPT2LMHeadModel, GPT2_CONFIGS, get_gpt2_config,
                                       cross_entropy_loss)
from deepspeed_tpu.models.llama import (LlamaConfig, LlamaForCausalLM, LLAMA_CONFIGS, get_llama_config)
from deepspeed_tpu.models.bert import (BertConfig, BertModel, BertForMaskedLM, BERT_CONFIGS,
                                       get_bert_config, bert_mlm_loss)
from deepspeed_tpu.models.opt import (OPTConfig, OPTForCausalLM, OPT_CONFIGS, get_opt_config)
from deepspeed_tpu.models.gpt_neox import (GPTNeoXConfig, GPTNeoXForCausalLM, GPT_NEOX_CONFIGS,
                                            get_gpt_neox_config)
from deepspeed_tpu.models.bloom import (BloomConfig, BloomForCausalLM, BLOOM_CONFIGS,
                                        get_bloom_config)
from deepspeed_tpu.models.t5 import (T5Config, T5ForConditionalGeneration, T5_CONFIGS,
                                     get_t5_config)
from deepspeed_tpu.models.falcon import (FalconConfig, FalconForCausalLM, FALCON_CONFIGS,
                                          get_falcon_config)
from deepspeed_tpu.models.gptj import (GPTJConfig, GPTJForCausalLM, GPTJ_CONFIGS,
                                       get_gptj_config)
from deepspeed_tpu.models.gpt_neo import (GPTNeoConfig, GPTNeoForCausalLM, GPT_NEO_CONFIGS,
                                          get_gpt_neo_config)
from deepspeed_tpu.models.clip import (CLIPTextConfig, CLIPTextModel, CLIP_TEXT_CONFIGS,
                                       get_clip_text_config)

from deepspeed_tpu.utils import trace as _trace  # noqa: E402

_trace.imported(__name__, _import_t0)
