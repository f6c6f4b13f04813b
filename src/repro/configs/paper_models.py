"""The paper's own benchmark models (Section V-A), as CNN configs.

These are the models SemiSFL was evaluated on; they drive the paper-table
benchmarks.  Image sizes / layer counts follow Section V-A; the customized
CNN is the 2-conv + FC(512) + softmax model used on SVHN.
Split layers (Section V-C): CNN@2, AlexNet@5, VGG13@10, VGG16@13 — expressed
here as conv-stage indices in our composable CNN builder.
"""
from repro.configs.base import ArchConfig, SemiSFLConfig, register


def _cnn(name, channels, fc, image_size, split, num_classes=10, dropout=0.0,
         pool_after=(), pool_to=0, source="SemiSFL paper §V-A"):
    return register(ArchConfig(
        name=name,
        arch_type="cnn",
        source=source,
        num_layers=len(channels),
        d_model=fc[-1] if fc else channels[-1],
        num_heads=1,
        num_kv_heads=1,
        d_ff=0,
        vocab_size=0,
        cnn_channels=channels,
        cnn_fc=fc,
        cnn_dropout=dropout,
        cnn_pool_after=pool_after,
        cnn_pool_to=pool_to,
        image_size=image_size,
        num_classes=num_classes,
        modality="image",
        semisfl=SemiSFLConfig(split_layer=split, proj_dim=64, proj_hidden=128,
                              queue_len=2048),
        dtype="float32",
    ))


# (i) customized CNN on SVHN: two 5x5 convs, FC 512, softmax 10
PAPER_CNN = _cnn("paper-cnn", channels=(32, 64), fc=(512,), image_size=32, split=2)

# (ii) AlexNet on CIFAR-10 (127 MB); 0.5 dropout on the FC-4096 stack
PAPER_ALEXNET = _cnn("paper-alexnet", channels=(64, 192, 384, 256, 256),
                     fc=(4096, 4096), image_size=32, split=5, dropout=0.5)

# (iii) VGG13 on STL-10 (508 MB); 0.5 dropout on the FC-4096 stack
PAPER_VGG13 = _cnn("paper-vgg13",
                   channels=(64, 64, 128, 128, 256, 256, 512, 512, 512, 512),
                   fc=(4096, 4096), image_size=96, split=10, dropout=0.5)

VGG16_CHANNELS = (64, 64, 128, 128, 256, 256, 256, 512, 512, 512, 512, 512,
                  512)

# (iv) VGG16 on IMAGE-100 (528 MB, 0.13B params); 0.5 FC dropout.  The
# legacy layout: it pools only where the width changes, so convs 11-13
# run at 18x18 and FC1 takes 9x9x512 inputs (201.8M parameters).  It
# stays because the benchmark's tiny CPU cell (bench/tests) is built
# from it and from bench/configs/paper-vgg16.json, which state that
# layout; vgg16-image100 below is the published model.
PAPER_VGG16 = _cnn("paper-vgg16", channels=VGG16_CHANNELS,
                   fc=(4096, 4096), image_size=144, split=13, num_classes=100,
                   dropout=0.5)

# (iv) VGG16 on IMAGE-100 as published: torchvision's vgg16
# (configuration D of arXiv:1409.1556) with max-pools after convs 2, 4,
# 7, 10 and 13, then AdaptiveAvgPool2d((7, 7)), whose 7x7x512 = 25088
# inputs to FC1 give the paper's 0.13B parameters (134,670,244 at 100
# classes).  At 144x144 the pools leave 4x4, which the average pool
# spreads to 7x7.
VGG16_IMAGE100 = _cnn("vgg16-image100", channels=VGG16_CHANNELS,
                      fc=(4096, 4096), image_size=144, split=13,
                      num_classes=100, dropout=0.5,
                      pool_after=(2, 4, 7, 10, 13), pool_to=7,
                      source="SemiSFL paper §V-A; torchvision vgg16 "
                             "(arXiv:1409.1556, configuration D)")
