"""pretrained= plumbing for the vision zoo.

Reference contract: python/paddle/vision/models/resnet.py:351-359 —
pretrained=True downloads-or-asserts; it never silently returns random
weights. Here the artifact sources are air-gapped-friendly (local paths,
$PADDLE_TPU_PRETRAINED_HOME, registered file:// urls) and name-compat
covers torch-convention state dicts (running_mean/var, (out,in) Linear).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.vision import models as M
from paddle_tpu.vision.models import _utils as MU


def _tiny_resnet_kwargs():
    return dict(num_classes=7)


def _save_artifact(path, model):
    sd = {k: np.asarray(v._array) for k, v in model.state_dict().items()}
    paddle.save(sd, str(path))


def test_pretrained_false_is_noop():
    m = M.resnet18(pretrained=False, **_tiny_resnet_kwargs())
    assert m.fc.weight.shape[-1] == 7


def _isolate_sources(monkeypatch, tmp_path):
    """Point every artifact search root at empty tmp dirs so a populated
    developer cache can't satisfy pretrained=True."""
    from paddle_tpu.utils import download as DL
    monkeypatch.setenv("PADDLE_TPU_PRETRAINED_HOME", str(tmp_path / "ph"))
    monkeypatch.setattr(DL, "WEIGHTS_HOME", str(tmp_path / "wh"))
    monkeypatch.setattr(MU, "PRETRAINED_REGISTRY", {})


def test_pretrained_true_without_artifact_raises(monkeypatch, tmp_path):
    _isolate_sources(monkeypatch, tmp_path)
    with pytest.raises(RuntimeError, match="resnet18.*no weights artifact"):
        M.resnet18(pretrained=True, **_tiny_resnet_kwargs())


def test_pretrained_path_hydrates(tmp_path):
    src = M.resnet18(**_tiny_resnet_kwargs())
    art = tmp_path / "resnet18.pdparams"
    _save_artifact(art, src)

    dst = M.resnet18(pretrained=str(art), **_tiny_resnet_kwargs())
    for (k, a), (k2, b) in zip(sorted(src.state_dict().items()),
                               sorted(dst.state_dict().items())):
        assert k == k2
        np.testing.assert_array_equal(np.asarray(a._array),
                                      np.asarray(b._array))


def test_pretrained_true_from_home_dir(monkeypatch, tmp_path):
    src = M.resnet18(**_tiny_resnet_kwargs())
    _save_artifact(tmp_path / "resnet18.pdparams", src)
    monkeypatch.setenv("PADDLE_TPU_PRETRAINED_HOME", str(tmp_path))

    dst = M.resnet18(pretrained=True, **_tiny_resnet_kwargs())
    np.testing.assert_array_equal(
        np.asarray(src.state_dict()["conv1.weight"]._array),
        np.asarray(dst.state_dict()["conv1.weight"]._array))


def test_pretrained_registered_source(monkeypatch, tmp_path):
    src = M.squeezenet1_0(num_classes=5)
    art = tmp_path / "sq.pdparams"
    _save_artifact(art, src)
    monkeypatch.setenv("PADDLE_TPU_PRETRAINED_HOME",
                       str(tmp_path / "empty"))
    monkeypatch.setenv("PADDLE_TPU_HOME", str(tmp_path / "cache"))
    monkeypatch.setattr(MU, "PRETRAINED_REGISTRY", {})
    # WEIGHTS_HOME is computed at import; re-point it for the monkeypatched
    # cache so the registered source lands in tmp
    from paddle_tpu.utils import download as DL
    monkeypatch.setattr(DL, "WEIGHTS_HOME",
                        str(tmp_path / "cache" / "weights"))
    MU.register_pretrained_source("squeezenet1_0", str(art))

    dst = M.squeezenet1_0(pretrained=True, num_classes=5)
    np.testing.assert_array_equal(
        np.asarray(src.state_dict()["features.0.weight"]._array)
        if "features.0.weight" in src.state_dict() else
        np.asarray(list(src.state_dict().values())[0]._array),
        np.asarray(list(dst.state_dict().values())[0]._array))


def test_torch_convention_compat(tmp_path):
    """running_mean/running_var renames, num_batches_tracked dropped,
    (out,in) Linear weights transposed — a torchvision-style dict loads."""
    src = M.resnet18(**_tiny_resnet_kwargs())
    sd = {k: np.asarray(v._array) for k, v in src.state_dict().items()}
    torch_sd = {}
    for k, v in sd.items():
        if k.endswith("._mean"):
            torch_sd[k[:-len("._mean")] + ".running_mean"] = v
        elif k.endswith("._variance"):
            torch_sd[k[:-len("._variance")] + ".running_var"] = v
        elif k == "fc.weight":
            torch_sd[k] = v.T  # torch Linear layout
        else:
            torch_sd[k] = v
    torch_sd["bn1.num_batches_tracked"] = np.asarray(3)
    art = tmp_path / "resnet18_torch.pdparams"
    paddle.save(torch_sd, str(art))

    dst = M.resnet18(pretrained=str(art), **_tiny_resnet_kwargs())
    np.testing.assert_array_equal(
        sd["fc.weight"], np.asarray(dst.state_dict()["fc.weight"]._array))
    np.testing.assert_array_equal(
        sd["bn1._mean"], np.asarray(dst.state_dict()["bn1._mean"]._array))


def test_torch_pth_artifact_with_wrapper_and_square_linear(tmp_path):
    """A torch.save checkpoint ({'state_dict': ...}) loads: every 2-D
    .weight is transposed by format (so square Linears are handled), BN
    stats renamed."""
    torch = pytest.importorskip("torch")
    src = M.alexnet(num_classes=9)
    sd = {}
    for k, v in src.state_dict().items():
        arr = np.asarray(v._array)
        if k.endswith(".weight") and arr.ndim == 2:
            arr = arr.T  # torch Linear layout
        sd[k] = torch.from_numpy(np.ascontiguousarray(arr))
    art = tmp_path / "alexnet.pth"
    torch.save({"state_dict": sd, "epoch": 3}, str(art))

    dst = M.alexnet(pretrained=str(art), num_classes=9)
    for k, v in src.state_dict().items():
        np.testing.assert_array_equal(
            np.asarray(v._array),
            np.asarray(dst.state_dict()[k]._array), err_msg=k)


def test_partial_artifact_refused_without_mutation(tmp_path):
    """Refusal happens BEFORE any parameter is overwritten."""
    from paddle_tpu.vision.models._utils import load_pretrained
    src = M.resnet18(**_tiny_resnet_kwargs())
    sd = {k: np.asarray(v._array) for k, v in src.state_dict().items()}
    sd.pop("fc.weight")
    sd["conv1.weight"] = sd["conv1.weight"] + 1.0
    art = tmp_path / "partial2.pdparams"
    paddle.save(sd, str(art))
    before = np.asarray(src.state_dict()["conv1.weight"]._array).copy()
    with pytest.raises(RuntimeError, match="missing"):
        load_pretrained(src, "resnet18", str(art))
    np.testing.assert_array_equal(
        before, np.asarray(src.state_dict()["conv1.weight"]._array))


def test_partial_artifact_refused(tmp_path):
    src = M.resnet18(**_tiny_resnet_kwargs())
    sd = {k: np.asarray(v._array) for k, v in src.state_dict().items()}
    sd.pop("fc.weight")
    art = tmp_path / "partial.pdparams"
    paddle.save(sd, str(art))
    with pytest.raises(RuntimeError, match="missing.*parameters"):
        M.resnet18(pretrained=str(art), **_tiny_resnet_kwargs())


def test_no_constructor_drops_the_flag(monkeypatch, tmp_path):
    """Every zoo constructor must route pretrained= to load_pretrained:
    with no artifact anywhere, pretrained=True always raises."""
    _isolate_sources(monkeypatch, tmp_path)
    # the 38 models are built only to be refused: zero-filled parameters
    # instead of the random initializers (~140 s of tier-1 at full size)
    from paddle_tpu.nn.layer import layers
    monkeypatch.setattr(
        layers, "_resolve_initializer",
        lambda *a: lambda shape, dtype: np.zeros(shape, dtype))
    ctors = ["resnet18", "resnet34", "resnet50", "resnet101", "resnet152",
             "resnext50_32x4d", "resnext50_64x4d", "resnext101_32x4d",
             "resnext101_64x4d", "resnext152_32x4d", "resnext152_64x4d",
             "wide_resnet50_2", "wide_resnet101_2", "alexnet",
             "densenet121", "densenet161", "densenet169", "densenet201",
             "densenet264", "googlenet", "inception_v3", "mobilenet_v1",
             "mobilenet_v2", "mobilenet_v3_small", "mobilenet_v3_large",
             "shufflenet_v2_x0_25", "shufflenet_v2_x0_33",
             "shufflenet_v2_x0_5", "shufflenet_v2_x1_0",
             "shufflenet_v2_x1_5", "shufflenet_v2_x2_0",
             "shufflenet_v2_swish", "squeezenet1_0", "squeezenet1_1",
             "vgg11", "vgg13", "vgg16", "vgg19"]
    for name in ctors:
        with pytest.raises(RuntimeError):
            getattr(M, name)(pretrained=True)
