"""Model zoo: config-text builders for the parity model families.

The reference ships models as hand-written config files
(``example/MNIST/MNIST.conf``, ``example/MNIST/MNIST_CONV.conf``,
``example/ImageNet/ImageNet.conf``); GoogLeNet has no reference config but
its layer zoo (split/ch_concat/padded pooling) makes it expressible
(SURVEY.md §6).  These builders emit the same ``netconfig=start/end`` config
language, so everything downstream (NetConfig, trainer, checkpointing,
wrapper) treats zoo models identically to user-written config files.
"""

from .zoo import (alexnet, googlenet, hybrid_lm, latent_lm, lenet, looped_lm,
                  mlp, resnet, transformer, vgg)

__all__ = ["alexnet", "googlenet", "hybrid_lm", "latent_lm", "lenet",
           "looped_lm", "mlp", "resnet", "transformer", "vgg"]
