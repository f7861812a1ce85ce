"""AlexNet as upstream cxxnet's ``example/ImageNet/ImageNet.conf`` defines it
(Krizhevsky et al. 2012: two grouped towers, LRN after pool1 and pool2): the
netconfig block and the solver settings this repo ships in
``example/ImageNet/ImageNet.conf``, copied here so that the program cannot
change the measured model.  The iterator sections are the traffic mix's.
"""

from __future__ import annotations

from typing import Any, Mapping

CONF = """\
netconfig=start
layer[0->1] = conv
  kernel_size = 11
  stride = 4
  nchannel = 96
layer[1->2] = relu
layer[2->3] = max_pooling
  kernel_size = 3
  stride = 2
layer[3->4] = lrn
  local_size = 5
  alpha = 0.001
  beta = 0.75
  knorm = 1
layer[4->5] = conv
  ngroup = 2
  nchannel = 256
  kernel_size = 5
  pad = 2
layer[5->6] = relu
layer[6->7] = max_pooling
  kernel_size = 3
  stride = 2
layer[7->8] = lrn
  local_size = 5
  alpha = 0.001
  beta = 0.75
  knorm = 1
layer[8->9] = conv
  nchannel = 384
  kernel_size = 3
  pad = 1
layer[9->10] = relu
layer[10->11] = conv
  nchannel = 384
  ngroup = 2
  kernel_size = 3
  pad = 1
layer[11->12] = relu
layer[12->13] = conv
  nchannel = 256
  ngroup = 2
  kernel_size = 3
  pad = 1
  init_bias = 1.0
layer[13->14] = relu
layer[14->15] = max_pooling
  kernel_size = 3
  stride = 2
layer[15->16] = flatten
layer[16->17] = fullc:fc6
  nhidden = 4096
  init_sigma = 0.005
  init_bias = 1.0
layer[17->18] = relu
layer[18->18] = dropout
  threshold = 0.5
layer[18->19] = fullc:fc7
  nhidden = 4096
  init_sigma = 0.005
  init_bias = 1.0
layer[19->20] = relu
layer[20->20] = dropout
  threshold = 0.5
layer[20->21] = fullc:fc8
  nhidden = 1000
layer[21->21] = softmax
netconfig=end

metric = error
metric = rec@1
metric = rec@5

input_shape = 3,227,227

momentum = 0.9
wmat:lr  = 0.01
wmat:wd  = 0.0005
bias:wd  = 0.000
bias:lr  = 0.02
lr:schedule = factor
lr:factor = 0.1
lr:step = 100000
random_type = xavier
dtype = bfloat16
"""


def conf_text(names: Mapping[str, Any]) -> str:
    """The same text for every cell: nothing in it depends on the mix."""
    return CONF
