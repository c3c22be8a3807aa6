"""Model zoo — graph-building functions with the reference's interfaces.

Reference parity: examples/cnn/models/ (LogReg, MLP, CNN, LeNet, AlexNet,
VGG, ResNet, RNN, LSTM), examples/nlp/bert/hetu_bert.py (BERT family),
examples/nlp/hetu_transformer.py (seq2seq Transformer),
examples/ctr/models/ (WDL, DeepFM, DCN, DC), examples/rec/hetu_ncf.py
(NCF/NeuMF), examples/gnn/gnn_model (GCN, GraphSAGE). Each builder takes
placeholder nodes and returns (loss, y) graph nodes, exactly like the
reference's ``model(x, y_)`` convention.
"""
from .cnn import (logreg, mlp, cnn_3_layers, digits_cnn, lenet, alexnet,
                  vgg16, vgg19, resnet18, resnet34, rnn, lstm)
from .gpt import GPTConfig, GPTModel, GPTLMHeadModel
from .latent_moe import LatentMoEConfig
from .ssm_hybrid import SSMHybridConfig
from .window_moe import WindowMoEConfig
from .shared_cache_decoder import SharedCacheConfig
from .sparse_decoder import (SparseDecoderConfig, SparseDecoderModel,
                             SparseDecoderLMHeadModel)
from .hybrid_decoder import (HybridDecoderConfig, HybridDecoderModel,
                             HybridDecoderLMHeadModel)
from .bert import (BertConfig, BertModel, BertForPreTraining,
                   BertForSequenceClassification, BertForMaskedLM)
from .ctr import (wdl_criteo, wdl_adult, deepfm_criteo, dcn_criteo,
                  dc_criteo)
from .gnn import gcn_layer, gcn, graphsage
from .ncf import neural_mf
from .transformer import Transformer, TransformerConfig
