"""ResNet-50 v1.5: He et al., arXiv:1512.03385, Table 1 (50-layer column),
with the stride-2 of each downsampling bottleneck on its 3x3 conv, as
torchvision's ``resnet50`` and the MLPerf Inference classification
benchmark run it.

- Stem: conv 7x7/2 -> 64 (pad 3), batchnorm, ReLU; max pool 3x3/2, pad 1.
- Four stages of (3, 4, 6, 3) bottlenecks, widths 64/128/256/512, outputs
  256/512/1024/2048: conv 1x1 -> 3x3 (stride 2 in the first block of
  stages 2-4) -> 1x1, each with batchnorm, ReLU after the first two.
- Each stage's first block has a 1x1 projection shortcut with batchnorm
  (stride 2 in stages 2-4), written as a ``route`` of the block input, the
  projection conv, then a ``shortcut`` to the main branch's last conv.
  Other blocks add their input back.  ReLU follows every add.
- Global average pool and fc 2048 -> 1000 (the ``fc`` layer pools a 4-D
  input).

75 layers: 53 convs, 4 routes, 16 shortcuts, 1 max pool, 1 fc.
"""
from repro.models.cnn import CNNLayer

C = CNNLayer

#: (width, blocks, stride of the first block) per stage.
STAGES = ((64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2))


def _conv(ch, k=1, s=1, activation="relu"):
    return C("conv", out_channels=ch, kernel=k, stride=s, batch_norm=True,
             activation=activation)


def layers(stem=64, stages=STAGES, classes=1000):
    """The layer table of a ResNet v1.5 of bottleneck ``stages``, each
    (width, blocks, stride); ResNet-50 by default."""
    table = [_conv(stem, 7, 2), C("maxpool", size=3, stride=2, pad=1)]
    block_in = len(table) - 1
    for width, blocks, stride in stages:
        for b in range(blocks):
            s = stride if b == 0 else 1
            table += [_conv(width), _conv(width, 3, s),
                      _conv(4 * width, activation="linear")]
            added = block_in                # identity: the block's input
            if b == 0:                      # projection of the block's input
                added = len(table) - 1
                table += [C("route", from_layers=(block_in,)),
                          _conv(4 * width, 1, s, activation="linear")]
            table.append(C("shortcut", from_layers=(added,),
                           activation="relu"))
            block_in = len(table) - 1
    table.append(C("fc", out_channels=classes, activation="linear",
                   batch_norm=False))
    return tuple(table)


LAYERS = layers()

INPUT_HW = (224, 224)
NAME = "resnet50"

# The facade descriptor: ``repro.compile(resnet50.MODEL, params, options)``.
from repro.api.model import CNNModel as _CNNModel  # noqa: E402

MODEL = _CNNModel(LAYERS, INPUT_HW, in_channels=3, name=NAME)
