"""Model families matching the reference's acceptance configs (BASELINE.md):

  #1 LeNet/MNIST       -> gluon.model_zoo.vision.lenet
  #2 ResNet-50/ImageNet -> gluon.model_zoo.vision.resnet
  #3 BERT base/large    -> models.bert       (GluonNLP scripts/bert shape)
  #4 Transformer WMT    -> models.transformer (GluonNLP machine_translation)
  #5 GPT-2 345M         -> models.gpt2

Plus detection: models.ssd (example/ssd + GluonCV SSD shape, exercising the
full contrib MultiBox family), and models.deepseek_v2 (latent attention,
group-limited routed experts of which one chip holds a share; served through
inference.GenerationEngine from a paged latent cache) and models.dots3_note
(latent attention over a learned subset of the cache beside window layers
with a latent of their own; two page groups in one engine), and
models.smallthinker (grouped key-value heads over paged K/V pools, window
layers with positions beside full layers without, a router that reads the
block's input, every ReLU-gated expert held), and models.olmo_hybrid (Gated
DeltaNet layers, whose recurrent state a paged engine keeps by slot, three
to one with full attention over paged K/V pools), and models.minicpm_sala
(block-sparse attention whose decode read walks a list of chosen blocks by
page, with a pool of compressed keys for the selector, one to three with
Lightning Attention layers whose decayed state is kept by slot).
"""
from . import deepseek_v2  # noqa: F401
from . import dots3_note  # noqa: F401
from . import smallthinker  # noqa: F401
from . import olmo_hybrid  # noqa: F401
from . import minicpm_sala  # noqa: F401
from . import bert  # noqa: F401
from . import gpt2  # noqa: F401
from . import ssd  # noqa: F401
from . import transformer  # noqa: F401
from .bert import BERTModel, BERTForPretrain, get_bert  # noqa: F401
from .deepseek_v2 import DeepseekV2Model, get_deepseek_v2  # noqa: F401
from .dots3_note import Dots3NoteModel, get_dots3_note  # noqa: F401
from .gpt2 import GPT2Model, get_gpt2  # noqa: F401
from .smallthinker import SmallThinkerModel, get_smallthinker  # noqa: F401
from .olmo_hybrid import OlmoHybridModel, get_olmo_hybrid  # noqa: F401
from .minicpm_sala import MiniCPMSALAModel, get_minicpm_sala  # noqa: F401
from .ssd import SSD, get_ssd  # noqa: F401
from .transformer import Transformer, get_transformer  # noqa: F401
