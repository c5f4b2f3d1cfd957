from bigdl_tpu.models.lenet import LeNet5
from bigdl_tpu.models.resnet import resnet_cifar, resnet50, BasicBlock, Bottleneck
from bigdl_tpu.models.inception import (inception_v1, inception_v2,
                                         inception_module,
                                         inception_v2_module)
from bigdl_tpu.models.vgg import vgg16, vgg_cifar10
from bigdl_tpu.models.rnn_zoo import char_rnn, Seq2Seq
from bigdl_tpu.models.autoencoder import Encoder, autoencoder
from bigdl_tpu.models.transformer_zoo import (
    TransformerEncoder, BERT, BERTClassifier,
)
from bigdl_tpu.models.recsys import NeuralCF, WideAndDeep
from bigdl_tpu.models.maskrcnn import MaskRCNN, maskrcnn_resnet50
from bigdl_tpu.models.mla_moe_lm import MLAMoEConfig, MLAMoELM

__all__ = [
    "LeNet5", "resnet_cifar", "resnet50", "BasicBlock", "Bottleneck",
    "inception_v1", "inception_v2", "inception_module", "inception_v2_module",
    "vgg16", "vgg_cifar10", "char_rnn",
    "Seq2Seq", "autoencoder", "Encoder", "TransformerEncoder", "BERT",
    "BERTClassifier", "NeuralCF", "WideAndDeep", "MaskRCNN",
    "maskrcnn_resnet50", "MLAMoEConfig", "MLAMoELM",
]
