"""Export/native coverage for the long-tail forward types — RBM,
tied-weight deconv, Kohonen (reference capability: libVeles
unit_factory.cc registers every forward unit type, so every trained
model is deployable; previously only the FC/conv families were)."""

import numpy
import pytest

import veles_tpu.prng as prng
from veles_tpu.export import ExportedModel, export_workflow
from veles_tpu.launcher import Launcher
from veles_tpu.native import NativeModel


def _sigmoid(v):
    return 1.0 / (1.0 + numpy.exp(-v))


@pytest.fixture(scope="module")
def rbm_artifact(tmp_path_factory):
    from veles_tpu.znicz.samples.mnist_rbm import MnistRBMWorkflow
    prng.reset()
    prng.get(0).seed(77)
    launcher = Launcher()
    wf = MnistRBMWorkflow(launcher, n_hidden=32, max_epochs=1,
                          learning_rate=0.1)
    launcher.initialize()
    launcher.run()
    path = str(tmp_path_factory.mktemp("rbm") / "rbm.veles.tgz")
    export_workflow(wf, path)
    return wf, path


@pytest.fixture(scope="module")
def ae_artifact(tmp_path_factory):
    from veles_tpu.znicz.samples.mnist_rbm import MnistAEWorkflow
    prng.reset()
    prng.get(0).seed(78)
    launcher = Launcher()
    wf = MnistAEWorkflow(launcher, n_hidden=32, max_epochs=1,
                         learning_rate=0.1)
    launcher.initialize()
    launcher.run()
    path = str(tmp_path_factory.mktemp("ae") / "ae.veles.tgz")
    export_workflow(wf, path)
    return wf, path


@pytest.fixture(scope="module")
def som_artifact(tmp_path_factory):
    from veles_tpu.znicz.samples.kohonen import KohonenWorkflow
    prng.reset()
    prng.get(0).seed(79)
    launcher = Launcher()
    wf = KohonenWorkflow(launcher, shape=(4, 4), max_epochs=2)
    launcher.initialize()
    launcher.run()
    path = str(tmp_path_factory.mktemp("som") / "som.veles.tgz")
    export_workflow(wf, path)
    return wf, path


def test_rbm_export_matches_unit(rbm_artifact):
    """Artifact forward == sigmoid(v·W + c) with the trained CD
    weights (RBM inference is its hidden-probability encoder)."""
    wf, path = rbm_artifact
    model = ExportedModel(path)
    assert [u["type"] for u in model.units] == ["rbm"]
    wf.rbm.weights.map_read()
    wf.rbm.bias.map_read()
    w = numpy.asarray(wf.rbm.weights.mem)
    c = numpy.asarray(wf.rbm.bias.mem)
    x = numpy.random.RandomState(0).rand(8, w.shape[0]) \
        .astype(numpy.float32)
    want = _sigmoid(x @ w + c)
    numpy.testing.assert_allclose(model.forward_numpy(x), want,
                                  rtol=1e-4, atol=1e-5)
    numpy.testing.assert_allclose(model.forward(x), want,
                                  rtol=1e-3, atol=1e-4)


def test_ae_export_ties_weights(ae_artifact):
    """The deconv entry must carry the encoder's weights transposed;
    the chain is encoder → decoder = sigmoid(h·Wᵀ + b_vis)."""
    wf, path = ae_artifact
    model = ExportedModel(path)
    assert [u["type"] for u in model.units] == \
        ["all2all_sigmoid", "all2all_deconv_sigmoid"]
    wf.encoder.weights.map_read()
    wf.encoder.bias.map_read()
    wf.decoder.vbias.map_read()
    w = numpy.asarray(wf.encoder.weights.mem)
    c = numpy.asarray(wf.encoder.bias.mem)
    b = numpy.asarray(wf.decoder.vbias.mem)
    x = numpy.random.RandomState(1).rand(8, w.shape[0]) \
        .astype(numpy.float32)
    h = _sigmoid(x @ w + c)
    want = _sigmoid(h @ w.T + b)
    numpy.testing.assert_allclose(model.forward_numpy(x), want,
                                  rtol=1e-4, atol=1e-5)


def test_kohonen_export_matches_unit(som_artifact):
    """Artifact forward emits the BMU distance map; argmin must agree
    with the live unit's winner assignment."""
    wf, path = som_artifact
    model = ExportedModel(path)
    assert [u["type"] for u in model.units] == ["kohonen"]
    wf.som.weights.map_read()
    w = numpy.asarray(wf.som.weights.mem)
    x = numpy.random.RandomState(2).rand(32, w.shape[1]) \
        .astype(numpy.float32)
    want = ((x * x).sum(1, keepdims=True) - 2.0 * (x @ w.T) +
            (w * w).sum(1))
    got = model.forward_numpy(x)
    numpy.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)
    assert (numpy.argmin(got, 1) == numpy.argmin(want, 1)).all()


def test_native_longtail_parity(rbm_artifact, ae_artifact,
                                som_artifact):
    """The C++ runtime executes all three new types bit-for-bit
    (within float tolerance) against the numpy mirror."""
    for _, path in (rbm_artifact, ae_artifact, som_artifact):
        py = ExportedModel(path)
        nat = NativeModel(path)
        assert nat.unit_types == [u["type"] for u in py.units]
        n_in = int(numpy.prod(py.input_shape))
        x = numpy.random.RandomState(3).rand(8, n_in) \
            .astype(numpy.float32)
        numpy.testing.assert_allclose(
            nat.forward(x), py.forward_numpy(x).reshape(8, -1),
            rtol=1e-4, atol=1e-5)


@pytest.fixture(scope="module")
def lm_artifact(tmp_path_factory):
    from veles_tpu.znicz.samples.tinylm import TinyLMWorkflow
    prng.reset()
    prng.get(0).seed(3)
    launcher = Launcher()
    wf = TinyLMWorkflow(launcher, max_epochs=8)
    launcher.initialize()
    launcher.run()
    path = str(tmp_path_factory.mktemp("lm") / "lm.veles.tgz")
    export_workflow(wf, path)
    return wf, path


def test_lm_export_all_paths_agree(lm_artifact):
    """Transformer LM artifact: numpy mirror == jitted jax chain ==
    native C++ runtime, and the deployed model still solves its
    task (first-token recall at 100%)."""
    wf, path = lm_artifact
    model = ExportedModel(path)
    assert [u["type"] for u in model.units] == \
        ["embedding", "transformer_block", "lm_head"]
    assert model.manifest["input"]["dtype"] == "int32"
    x = numpy.random.RandomState(0).randint(
        0, 16, (6, 32)).astype(numpy.float32)
    a = model.forward_numpy(x)
    b = numpy.asarray(model.forward(x))
    numpy.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)
    nat = NativeModel(path)
    c = nat.forward(x)
    numpy.testing.assert_allclose(c, a.reshape(6, -1), rtol=1e-4,
                                  atol=1e-4)
    pred = numpy.argmax(a, -1)
    assert (pred == x[:, :1].astype(int)).mean() == 1.0


def test_lm_export_ties_head_to_embedding(lm_artifact):
    """The tied LM head materializes the embedding weights transposed
    so the artifact stands alone."""
    wf, path = lm_artifact
    model = ExportedModel(path)
    head = model.units[-1]
    w = model.weights[head["params"]["weights"]]
    wf.embedding.weights.map_read()
    numpy.testing.assert_array_equal(
        w, numpy.asarray(wf.embedding.weights.mem).T)


def test_lm_export_clamps_oov_tokens(lm_artifact):
    """Out-of-range token ids clamp identically in all three paths
    (the numpy mirror must not raise/wrap where native/jax clamp)."""
    _wf, path = lm_artifact
    model = ExportedModel(path)
    nat = NativeModel(path)
    x = numpy.array([[99, -3] + [1] * 30], numpy.float32)
    a = model.forward_numpy(x)
    b = numpy.asarray(model.forward(x))
    c = nat.forward(x)
    numpy.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)
    numpy.testing.assert_allclose(c, a.reshape(1, -1), rtol=1e-4,
                                  atol=1e-4)
