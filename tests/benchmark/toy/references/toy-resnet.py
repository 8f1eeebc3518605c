FAMILY = "resnet_v1"
BUILDER = "resnet_v1"
