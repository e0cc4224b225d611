"""``references/resnet.py`` against PyTorch modules built as torchvision's
``resnet50`` builds them: the stem (``Conv2d(3, 64, 7, 2, 3)``,
``BatchNorm2d``, ReLU, ``MaxPool2d(3, 2, 1)``) and one v1.5 downsampling
``Bottleneck`` (stride 2 on its 3x3 conv and its 1x1 projection), on the
same seeded weights.  This ties the reference, and so the comparison that
decides ``correct``, to the published semantics rather than to the program.
"""
import numpy as np
import pytest

torch = pytest.importorskip("torch")
nn = torch.nn

import jax  # noqa: E402

from harness.cell import seed_streams  # noqa: E402
from references import resnet  # noqa: E402


class Bottleneck(nn.Module):
    """torchvision's ``Bottleneck`` (expansion 4, stride on conv2)."""

    def __init__(self, cin, width, stride):
        super().__init__()
        self.conv1 = nn.Conv2d(cin, width, 1, bias=False)
        self.bn1 = nn.BatchNorm2d(width)
        self.conv2 = nn.Conv2d(width, width, 3, stride, 1, bias=False)
        self.bn2 = nn.BatchNorm2d(width)
        self.conv3 = nn.Conv2d(width, 4 * width, 1, bias=False)
        self.bn3 = nn.BatchNorm2d(4 * width)
        self.relu = nn.ReLU()
        self.downsample = nn.Sequential(
            nn.Conv2d(cin, 4 * width, 1, stride, bias=False),
            nn.BatchNorm2d(4 * width))

    def forward(self, x):
        out = self.relu(self.bn1(self.conv1(x)))
        out = self.relu(self.bn2(self.conv2(out)))
        out = self.bn3(self.conv3(out))
        return self.relu(out + self.downsample(x))


def _stem():
    return nn.Sequential(nn.Conv2d(3, 64, 7, 2, 3, bias=False),
                         nn.BatchNorm2d(64), nn.ReLU(), nn.MaxPool2d(3, 2, 1))


#: The same network as a layer table, the configuration file's encoding.
LAYERS = [
    {"kind": "conv", "out_channels": 64, "kernel": 7, "stride": 2, "batch_norm": True, "activation": "relu"},
    {"kind": "maxpool", "size": 3, "stride": 2, "pad": 1},
    {"kind": "conv", "out_channels": 32, "kernel": 1, "stride": 1, "batch_norm": True, "activation": "relu"},
    {"kind": "conv", "out_channels": 32, "kernel": 3, "stride": 2, "batch_norm": True, "activation": "relu"},
    {"kind": "conv", "out_channels": 128, "kernel": 1, "stride": 1, "batch_norm": True, "activation": "linear"},
    {"kind": "route", "from_layers": [1]},
    {"kind": "conv", "out_channels": 128, "kernel": 1, "stride": 2, "batch_norm": True, "activation": "linear"},
    {"kind": "shortcut", "from_layers": [4], "activation": "relu"},
]


def _load(conv, bn, p):
    """Copy a reference conv (HWIO) + batchnorm into torch (OIHW)."""
    conv.weight.data = torch.from_numpy(
        np.ascontiguousarray(np.asarray(p["w"]).transpose(3, 2, 0, 1)))
    for t, name in ((bn.weight, "gamma"), (bn.bias, "beta"),
                    (bn.running_mean, "mean"), (bn.running_var, "var")):
        t.data = torch.from_numpy(np.asarray(p["bn"][name]))
    bn.eps = resnet.BN_EPS


@pytest.mark.parametrize("seed", [2**33 + 3, 4100000021])
def test_reference_matches_torch(seed):
    key, rng = seed_streams(seed)
    params = resnet.init_params(key, LAYERS, 3)
    x = rng.standard_normal((2, 33, 31, 3), dtype=np.float32)
    want = np.asarray(jax.jit(lambda p, xx: resnet.forward(p, LAYERS, xx))(
        params, x))

    stem, block = _stem(), Bottleneck(64, 32, 2)
    _load(stem[0], stem[1], params[0])
    _load(block.conv1, block.bn1, params[2])
    _load(block.conv2, block.bn2, params[3])
    _load(block.conv3, block.bn3, params[4])
    _load(block.downsample[0], block.downsample[1], params[6])
    net = nn.Sequential(stem, block).eval()
    with torch.no_grad():
        got = net(torch.from_numpy(x.transpose(0, 3, 1, 2))).numpy()
    got = got.transpose(0, 2, 3, 1)
    assert got.shape == want.shape == (2, 5, 4, 128)
    # fp32 on both sides, summed in different orders: rounding only.
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err < 1e-6, err
