"""Model zoo.

Parity: /root/reference/benchmark/fluid/models/* + fluid tests/book
models, rebuilt on paddle_tpu layers. Each module exposes
`build(...) -> (feeds, fetches)`-style builders usable inside
program_guard. Beside the Fluid book's models, three decoder-only
language models built from `fluid.layers` alone, each at its published
config and told which share of a deployment it holds: `lfm2_moe` (gated
short convolutions, grouped-query attention, sparse experts; one
expert-parallel rank), `solar_open2` (gated delta-rule linear attention,
gated attention without positions, a shared expert beside sparse
experts; one rank of tensor- and expert-parallel groups) and `mellum2`
(sliding-window and full attention layers mixed: `flash_attention`'s
`window`; rotary positions by layer type with YaRN on the full layers:
`rotary_embedding`'s `rope_type="yarn"`; a softmax top-k router:
`moe_route`'s `scoring="softmax"`; one expert-parallel rank).
"""
from . import mnist
from . import vgg
from . import resnet
from . import se_resnext
from . import transformer
from . import stacked_lstm
from . import deepfm
from . import word2vec
from . import srl
from . import recommender
from . import sentiment
from . import fit_a_line
from . import ssd
from . import crnn_ctc
from . import faster_rcnn
from . import dcgan
from . import seq2seq
from . import resnet_with_preprocess
from . import lfm2_moe
from . import solar_open2
from . import mellum2
