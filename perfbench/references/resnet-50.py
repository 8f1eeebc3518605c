"""ResNet-50 v1 (He et al. 2015; stride on the 3x3 convolution, as the
program's image-classification example builds it).  The plain reference is
the family's (``perfbench/models/resnet_v1.py``)."""
FAMILY = "resnet_v1"
BUILDER = "resnet_v1"
