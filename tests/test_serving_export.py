"""Servable export: StableHLO + npz that serve WITHOUT the framework
(VERDICT r2 #6 — the reference's SavedModel role, callbacks.py:23-66).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from elasticdl_tpu.models import mnist
from elasticdl_tpu.models.callbacks import ModelExporter
from elasticdl_tpu.worker.collective_trainer import CollectiveTrainer


def _trained_export(tmp_path):
    spec = mnist.model_spec()
    trainer = CollectiveTrainer(spec, batch_size=8)
    xs, ys = mnist.synthetic_data(n=8)
    trainer.train_minibatch(xs, ys)
    export_dir = str(tmp_path / "export")
    ModelExporter(export_dir, model_name="mnist").on_train_end(trainer)
    return trainer, export_dir, xs


def test_servable_layout_and_manifest(tmp_path):
    _, export_dir, _ = _trained_export(tmp_path)
    for fname in ("model.npz", "model.stablehlo", "manifest.json"):
        assert os.path.exists(os.path.join(export_dir, fname)), fname
    with open(os.path.join(export_dir, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["format"] == "elasticdl_tpu_servable_v2"
    assert manifest["model_name"] == "mnist"
    assert "tpu" in manifest["platforms"]
    sig = manifest["input_signature"]
    assert sig["shape"][1:] == [28, 28]


def test_servable_matches_trainer_predictions(tmp_path):
    trainer, export_dir, xs = _trained_export(tmp_path)
    from elasticdl_tpu.serving.loader import load_servable

    model = load_servable(export_dir)
    got = np.asarray(model.predict(np.asarray(xs)))
    want = trainer.predict_minibatch(xs)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5,
                               atol=1e-5)


_STANDALONE = r"""
import json
import sys

import numpy as np

sys.path.insert(0, %(repo)r)
import jax

jax.config.update("jax_platforms", "cpu")

from elasticdl_tpu.serving.loader import load_servable

model = load_servable(%(export_dir)r)
shape = [
    8 if d is None else d  # polymorphic batch: caller picks the batch
    for d in model.manifest["input_signature"]["shape"]
]
x = np.zeros(shape, np.float32)
out = np.asarray(model.predict(x))
banned = [
    m for m in sys.modules
    if m.startswith(("elasticdl_tpu.master", "elasticdl_tpu.worker",
                     "elasticdl_tpu.ps", "elasticdl_tpu.models"))
]
print(json.dumps({"shape": list(out.shape), "banned": banned}))
"""


def test_servable_loads_without_framework(tmp_path):
    """The VERDICT 'done' bar: a fresh process loads the export and runs
    inference importing NOTHING from master/worker/ps (nor the model
    zoo)."""
    _, export_dir, _ = _trained_export(tmp_path)
    code = _STANDALONE % {
        "repo": os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
        "export_dir": export_dir,
    }
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=240, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["banned"] == []
    assert result["shape"] == [8, 10]


def test_dense_overrides_take_precedence(tmp_path):
    from elasticdl_tpu.serving.export import export_servable
    from elasticdl_tpu.serving.loader import load_servable

    params = {"w": np.ones((4, 2), np.float32)}
    newer = {"w": np.full((4, 2), 3.0, np.float32)}
    export_servable(
        str(tmp_path / "e"),
        lambda p, x: x @ p["w"],
        params,
        np.zeros((1, 4), np.float32),
        dense_overrides=newer,
        platforms=("cpu",),
    )
    model = load_servable(str(tmp_path / "e"))
    np.testing.assert_array_equal(model.params["w"], newer["w"])
    out = np.asarray(model.predict(np.ones((1, 4), np.float32)))
    np.testing.assert_allclose(out, np.full((1, 2), 12.0))


def test_embedding_lookup(tmp_path):
    from elasticdl_tpu.serving.export import export_servable
    from elasticdl_tpu.serving.loader import load_servable

    export_servable(
        str(tmp_path / "e"),
        lambda p, x: x * p["s"],
        {"s": np.float32(2.0)},
        np.zeros((2, 3), np.float32),
        embeddings={"users": (np.array([5, 9]),
                              np.arange(8, dtype=np.float32)
                              .reshape(2, 4))},
        platforms=("cpu",),
    )
    model = load_servable(str(tmp_path / "e"))
    assert model.manifest["embedding_tables"] == ["users"]
    rows = model.lookup_embedding("users", [9, 7, 5])
    np.testing.assert_array_equal(rows[0], [4, 5, 6, 7])
    np.testing.assert_array_equal(rows[1], [0, 0, 0, 0])  # unknown id
    np.testing.assert_array_equal(rows[2], [0, 1, 2, 3])


def test_polymorphic_batch_export(tmp_path):
    """The servable accepts ANY batch size (symbolic leading dim), and
    a scalar aux input does not force the export monomorphic."""
    from elasticdl_tpu.serving.export import export_servable
    from elasticdl_tpu.serving.loader import load_servable

    manifest = export_servable(
        str(tmp_path / "e"),
        lambda p, x: x["v"] @ p["w"] * x["temp"],
        {"w": np.arange(8, dtype=np.float32).reshape(4, 2)},
        {"v": np.zeros((1, 4), np.float32),
         "temp": np.float32(1.0)},  # rank-0 leaf stays concrete
        platforms=("cpu",),
    )
    assert manifest["polymorphic_batch"] is True
    # metadata tells the truth: the batch dim is free, not the
    # example's 1 (rank-0 leaves keep their empty shape)
    assert manifest["input_signature"]["v"]["shape"] == [None, 4]
    assert manifest["input_signature"]["temp"]["shape"] == []
    model = load_servable(str(tmp_path / "e"))
    for batch in (1, 3, 7):  # != the example's batch of 1
        out = np.asarray(model.predict(
            {"v": np.ones((batch, 4), np.float32),
             "temp": np.float32(2.0)}
        ))
        assert out.shape == (batch, 2)
        np.testing.assert_allclose(out[0], [24.0, 32.0])

    # Inputs that DISAGREE on their leading dim must not get a shared
    # batch symbol (the export would succeed but reject its own example
    # shapes at serving time): fixed-shape export instead.
    manifest2 = export_servable(
        str(tmp_path / "e2"),
        lambda p, x: x["a"].sum() + x["b"].sum() + p["w"],
        {"w": np.float32(0.0)},
        {"a": np.zeros((2, 3), np.float32),
         "b": np.zeros((5,), np.float32)},
        platforms=("cpu",),
    )
    assert manifest2["polymorphic_batch"] is False
    model2 = load_servable(str(tmp_path / "e2"))
    out2 = model2.predict({"a": np.ones((2, 3), np.float32),
                           "b": np.ones((5,), np.float32)})
    np.testing.assert_allclose(np.asarray(out2), 11.0)


def test_model_server_rest_surface(tmp_path):
    """The TF-Serving-role HTTP server over a servable export:
    metadata, :predict (instances), :lookup, and error paths — the
    REST shape clients of the reference's TF Serving deployment keep
    (model_handler.py:242-269)."""
    import json as _json
    import threading
    import urllib.error
    import urllib.request

    from elasticdl_tpu.serving.export import export_servable
    from elasticdl_tpu.serving.server import ModelEndpoint, build_server

    export_servable(
        str(tmp_path / "e"),
        lambda p, x: x @ p["w"],
        {"w": np.arange(8, dtype=np.float32).reshape(4, 2)},
        np.zeros((1, 4), np.float32),
        model_name="lin",
        embeddings={"users": (np.array([5, 9]),
                              np.arange(8, dtype=np.float32)
                              .reshape(2, 4))},
        platforms=("cpu",),
    )
    server = build_server(ModelEndpoint(str(tmp_path / "e")), port=0)
    port = server.server_address[1]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    base = "http://127.0.0.1:%d/v1/models/lin" % port

    def call(path, payload=None):
        req = urllib.request.Request(
            base + path,
            data=None if payload is None
            else _json.dumps(payload).encode(),
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            return _json.loads(resp.read())

    try:
        meta = call("")
        assert meta["model_version_status"][0]["state"] == "AVAILABLE"
        assert meta["metadata"]["model_name"] == "lin"

        out = call(":predict", {"instances": [[1, 1, 1, 1],
                                              [0, 1, 0, 0]]})
        np.testing.assert_allclose(out["predictions"],
                                   [[12.0, 16.0], [2.0, 3.0]])

        vecs = call(":lookup", {"table": "users", "ids": [9, 7]})
        np.testing.assert_allclose(vecs["vectors"],
                                   [[4, 5, 6, 7], [0, 0, 0, 0]])

        with pytest.raises(urllib.error.HTTPError) as err:
            call(":predict", {"wrong_key": []})
        assert err.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as err:
            call(":nope", {})
        assert err.value.code == 404
    finally:
        server.shutdown()
        server.server_close()


def test_model_server_concurrent_predicts(tmp_path):
    """N threads hammer :predict concurrently; the endpoint lock keeps
    results correct and every request gets a response."""
    import json as _json
    import threading
    import urllib.request

    from elasticdl_tpu.serving.export import export_servable
    from elasticdl_tpu.serving.server import ModelEndpoint, build_server

    export_servable(
        str(tmp_path / "e"),
        lambda p, x: x * p["s"],
        {"s": np.float32(3.0)},
        np.zeros((1, 2), np.float32),
        model_name="c",
        platforms=("cpu",),
    )
    server = build_server(ModelEndpoint(str(tmp_path / "e")), port=0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    url = "http://127.0.0.1:%d/v1/models/c:predict" % port
    results = {}

    def hit(k):
        req = urllib.request.Request(
            url, data=_json.dumps(
                {"instances": [[k, k + 1]]}).encode())
        with urllib.request.urlopen(req, timeout=60) as resp:
            results[k] = _json.loads(resp.read())["predictions"]

    try:
        threads = [threading.Thread(target=hit, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert len(results) == 8
        for k, out in results.items():
            np.testing.assert_allclose(out, [[3.0 * k, 3.0 * (k + 1)]])
    finally:
        server.shutdown()
        server.server_close()


def test_embedding_lookup_duplicate_ids_keep_last(tmp_path):
    """A merged table carrying a duplicated id must serve the LAST
    stored row for it (the semantics of the dict-rebuild path the
    sorted index replaced — advisor r4)."""
    from elasticdl_tpu.serving.export import export_servable
    from elasticdl_tpu.serving.loader import load_servable

    ids = np.array([5, 9, 5])  # id 5 appears twice; last row wins
    values = np.arange(12, dtype=np.float32).reshape(3, 4)
    export_servable(
        str(tmp_path / "e"),
        lambda p, x: x * p["s"],
        {"s": np.float32(1.0)},
        np.zeros((2, 3), np.float32),
        embeddings={"users": (ids, values)},
        platforms=("cpu",),
    )
    model = load_servable(str(tmp_path / "e"))
    rows = model.lookup_embedding("users", [5, 9])
    np.testing.assert_array_equal(rows[0], [8, 9, 10, 11])
    np.testing.assert_array_equal(rows[1], [4, 5, 6, 7])


def test_versioned_serving_hot_reload(tmp_path):
    """TF-Serving layout <base>/<N>/: the server serves the latest
    complete version and flips to v2 exported MID-SERVE without a
    restart (VERDICT r4 #6); an incomplete version dir (no manifest
    yet) is ignored."""
    import json as _json
    import threading
    import time as _time
    import urllib.request

    from elasticdl_tpu.serving.export import export_servable
    from elasticdl_tpu.serving.server import ModelEndpoint, build_server

    base = str(tmp_path / "models")

    def put(version, scale):
        export_servable(
            os.path.join(base, str(version)),
            lambda p, x: x * p["s"],
            {"s": np.float32(scale)},
            np.zeros((1, 2), np.float32),
            model_name="vm", version=version,
            platforms=("cpu",),
        )

    put(1, 2.0)
    # An in-flight export (files but no manifest yet) must never be
    # picked up.
    os.makedirs(os.path.join(base, "7"))
    with open(os.path.join(base, "7", "model.npz"), "wb") as f:
        f.write(b"partial")

    endpoint = ModelEndpoint(base, poll_interval=0.05)
    server = build_server(endpoint, port=0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    root = "http://127.0.0.1:%d/v1/models/vm" % port

    def call(path, payload=None):
        req = urllib.request.Request(
            root + path,
            data=None if payload is None
            else _json.dumps(payload).encode(),
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            return _json.loads(resp.read())

    try:
        meta = call("/metadata")  # the TF-Serving metadata alias
        assert meta["model_version_status"][0]["version"] == "1"
        out = call(":predict", {"instances": [[1, 10]]})
        np.testing.assert_allclose(out["predictions"], [[2.0, 20.0]])

        put(2, 5.0)
        deadline = _time.monotonic() + 30
        while _time.monotonic() < deadline:
            meta = call("")
            if meta["model_version_status"][0]["version"] == "2":
                break
            _time.sleep(0.05)
        assert meta["model_version_status"][0]["version"] == "2"
        out = call(":predict", {"instances": [[1, 10]]})
        np.testing.assert_allclose(out["predictions"], [[5.0, 50.0]])
    finally:
        server.shutdown()
        server.server_close()


def test_embedding_lookup_large_table_is_o_batch(tmp_path):
    """100k-row table: lookups must use the index built once in
    __init__, not rebuild an O(table) dict per call (VERDICT r3 #7)."""
    import time

    from elasticdl_tpu.serving.export import export_servable
    from elasticdl_tpu.serving.loader import load_servable

    n = 100_000
    rng = np.random.RandomState(0)
    ids = rng.permutation(n * 2)[:n]  # unsorted, sparse id space
    values = rng.randn(n, 8).astype(np.float32)
    export_servable(
        str(tmp_path / "e"),
        lambda p, x: x * p["s"],
        {"s": np.float32(1.0)},
        np.zeros((2, 3), np.float32),
        embeddings={"items": (ids, values)},
        platforms=("cpu",),
    )
    model = load_servable(str(tmp_path / "e"))
    query = np.concatenate([ids[:64], [n * 2 + 7]])  # 64 hits + 1 miss
    t0 = time.perf_counter()
    for _ in range(100):
        rows = model.lookup_embedding("items", query)
    per_call = (time.perf_counter() - t0) / 100
    np.testing.assert_allclose(rows[:64], values[:64])
    np.testing.assert_array_equal(rows[64], np.zeros(8, np.float32))
    # The old dict-rebuild path costs ~30ms/call at 100k rows; the
    # searchsorted path is far under 5ms even on a loaded CI box.
    assert per_call < 0.005, "lookup is O(table): %.1f ms" % (
        per_call * 1e3)


def test_int8_quantized_export_roundtrip(tmp_path):
    """quantize='int8': weights-only per-channel int8 — ~4x smaller
    model.npz, loader dequantizes, predictions within quantization
    noise of the f32 export; small arrays ride through exact."""
    from elasticdl_tpu.serving.export import export_servable
    from elasticdl_tpu.serving.loader import load_servable

    rng = np.random.RandomState(0)
    params = {"w": rng.randn(256, 128).astype(np.float32),
              "b": rng.randn(128).astype(np.float32)}

    def apply_fn(p, x):
        return x @ p["w"] + p["b"]

    x = rng.randn(4, 256).astype(np.float32)
    for sub, quantize in (("f32", None), ("q8", "int8")):
        manifest = export_servable(
            str(tmp_path / sub), apply_fn, params,
            np.zeros((1, 256), np.float32), platforms=("cpu",),
            quantize=quantize,
        )
        if quantize:
            assert manifest["quantized_int8"] == ["w"]

    size_f32 = os.path.getsize(str(tmp_path / "f32" / "model.npz"))
    size_q8 = os.path.getsize(str(tmp_path / "q8" / "model.npz"))
    assert size_q8 < 0.35 * size_f32, (size_q8, size_f32)

    full = load_servable(str(tmp_path / "f32"))
    quant = load_servable(str(tmp_path / "q8"))
    np.testing.assert_array_equal(quant.params["b"], params["b"])
    want = np.asarray(full.predict(x))
    got = np.asarray(quant.predict(x))
    # Weight rounding ~scale/2 ~= max|w|/254 per element accumulates
    # ~sqrt(256)x over the length-256 dot: expect |err| well under 1
    # on outputs of magnitude ~10-30 (rtol alone would fail on the
    # near-zero outputs).
    np.testing.assert_allclose(got, want, rtol=0.05, atol=0.6)
    assert np.abs(got - want).max() > 1e-4  # it really quantized


def test_loader_rejects_unknown_feature_prefix(tmp_path):
    """A future feature prefix this loader copy doesn't understand
    must fail at LOAD time, not deep inside predict."""
    from elasticdl_tpu.serving.export import export_servable
    from elasticdl_tpu.serving.loader import load_servable

    export_servable(
        str(tmp_path / "e"), lambda p, x: x * p["s"],
        {"s": np.float32(2.0)}, np.zeros((1, 2), np.float32),
        platforms=("cpu",),
    )
    manifest_path = str(tmp_path / "e" / "manifest.json")
    with open(manifest_path) as f:
        manifest = json.load(f)
    manifest["format"] = "int4-weights+" + manifest["format"]
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(ValueError, match="known feature prefixes"):
        load_servable(str(tmp_path / "e"))


def test_int8_quantized_embedding_tables(tmp_path):
    """quantize='int8' also covers embedding tables (the dominant CTR
    artifact): per-row int8 storage, transparent dequant in BOTH
    loaders, lookups within rounding noise; tiny tables ride through
    exact."""
    from elasticdl_tpu.models.callbacks import load_export
    from elasticdl_tpu.serving.export import export_servable
    from elasticdl_tpu.serving.loader import load_servable

    rng = np.random.RandomState(0)
    big_vals = rng.randn(1024, 16).astype(np.float32)
    small_vals = rng.randn(3, 4).astype(np.float32)
    manifest = export_servable(
        str(tmp_path / "e"),
        lambda p, x: x * p["s"],
        {"s": np.float32(1.0)},
        np.zeros((2, 3), np.float32),
        embeddings={
            "items": (np.arange(1024), big_vals),
            "tiny": (np.array([5, 9, 11]), small_vals),
        },
        platforms=("cpu",), quantize="int8",
    )
    assert manifest["quantized_int8"] == ["emb:items"]
    model = load_servable(str(tmp_path / "e"))
    rows = model.lookup_embedding("items", [0, 7, 1023])
    np.testing.assert_allclose(
        rows, big_vals[[0, 7, 1023]], rtol=0.02, atol=0.05)
    np.testing.assert_array_equal(
        model.lookup_embedding("tiny", [9]), small_vals[[1]])
    # load_export (the training-side loader) dequantizes too
    _, embeddings = load_export(str(tmp_path / "e"))
    np.testing.assert_allclose(
        embeddings["items"][1], big_vals, rtol=0.02, atol=0.05)


def test_generate_servable_over_http(tmp_path):
    """LLM decode serving: export_generate compiles the batched
    prefill + KV-cache decode loop INTO the servable; the stock HTTP
    server then serves token generation via :predict with zero model
    code — and the artifact is token-exact against library-side
    generate."""
    import json as _json
    import threading
    import urllib.request

    import jax

    from elasticdl_tpu.models import transformer as tfm
    from elasticdl_tpu.serving.server import ModelEndpoint, build_server

    cfg = tfm.TransformerConfig(
        vocab_size=128, dim=32, num_heads=4, num_layers=2,
        max_seq_len=32, dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    manifest = tfm.export_generate(
        str(tmp_path / "gen"), params, cfg, max_new_tokens=6,
        prompt_len=8, model_name="lm", platforms=("cpu",))
    assert manifest["polymorphic_batch"] is True
    with pytest.raises(ValueError, match="exceeds max_seq_len"):
        tfm.export_generate(str(tmp_path / "bad"), params, cfg,
                            max_new_tokens=30, prompt_len=8)

    server = build_server(ModelEndpoint(str(tmp_path / "gen")), port=0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    prompt = np.arange(16, dtype=np.int32).reshape(2, 8) % 128
    try:
        req = urllib.request.Request(
            "http://127.0.0.1:%d/v1/models/lm:predict" % port,
            data=_json.dumps({"instances": prompt.tolist()}).encode())
        with urllib.request.urlopen(req, timeout=60) as resp:
            out = np.asarray(_json.loads(resp.read())["predictions"])
        assert out.shape == (2, 14)
        want = np.asarray(tfm.generate(params, cfg, prompt,
                                       max_new_tokens=6))
        np.testing.assert_array_equal(out, want)
    finally:
        server.shutdown()
        server.server_close()


def test_sampled_generate_servable(tmp_path):
    """temperature > 0 exports a SAMPLING servable with a per-request
    seed: equal seeds reproduce exactly, different seeds diverge, and
    everything stays in-vocab past the prompt."""
    import jax

    from elasticdl_tpu.models import transformer as tfm
    from elasticdl_tpu.serving.loader import load_servable

    cfg = tfm.TransformerConfig(
        vocab_size=128, dim=32, num_heads=4, num_layers=2,
        max_seq_len=32, dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tfm.export_generate(
        str(tmp_path / "s"), params, cfg, max_new_tokens=6,
        prompt_len=4, temperature=0.9, platforms=("cpu",))
    model = load_servable(str(tmp_path / "s"))
    prompt = np.arange(8, dtype=np.int32).reshape(2, 4)
    one = np.asarray(model.predict(
        {"prompt": prompt, "seed": np.int32(7)}))
    same = np.asarray(model.predict(
        {"prompt": prompt, "seed": np.int32(7)}))
    other = np.asarray(model.predict(
        {"prompt": prompt, "seed": np.int32(8)}))
    np.testing.assert_array_equal(one, same)  # seed reproduces
    assert not np.array_equal(one, other)     # seed matters
    assert one.shape == (2, 10)
    np.testing.assert_array_equal(one[:, :4], prompt)
    assert ((one >= 0) & (one < 128)).all()


def test_export_generate_rejects_negative_temperature(tmp_path):
    import jax

    from elasticdl_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=64, dim=16, num_heads=2,
                                num_layers=1, max_seq_len=16,
                                dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="temperature"):
        tfm.export_generate(str(tmp_path / "t"), params, cfg,
                            max_new_tokens=4, prompt_len=4,
                            temperature=-0.5)


def test_multi_model_server(tmp_path):
    """One server process hosts several models (the TF-Serving
    model-config role): each under its own /v1/models/<name> tree,
    unknown names 404 listing the hosted set."""
    import json as _json
    import threading
    import urllib.error
    import urllib.request

    from elasticdl_tpu.serving.export import export_servable
    from elasticdl_tpu.serving.server import ModelEndpoint, build_server

    for name, scale in (("a", 2.0), ("b", 5.0)):
        export_servable(
            str(tmp_path / name), lambda p, x: x * p["s"],
            {"s": np.float32(scale)}, np.zeros((1, 2), np.float32),
            model_name=name, platforms=("cpu",))
    server = build_server(
        [ModelEndpoint(str(tmp_path / "a")),
         ModelEndpoint(str(tmp_path / "b"))], port=0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()

    def predict(name, x):
        req = urllib.request.Request(
            "http://127.0.0.1:%d/v1/models/%s:predict" % (port, name),
            data=_json.dumps({"instances": x}).encode())
        with urllib.request.urlopen(req, timeout=30) as resp:
            return _json.loads(resp.read())["predictions"]

    try:
        with urllib.request.urlopen(
            "http://127.0.0.1:%d/healthz" % port, timeout=10
        ) as resp:
            assert _json.loads(resp.read()) == {"status": "ok"}
        np.testing.assert_allclose(predict("a", [[1, 2]]), [[2., 4.]])
        np.testing.assert_allclose(predict("b", [[1, 2]]), [[5., 10.]])
        with pytest.raises(urllib.error.HTTPError) as err:
            predict("c", [[1, 2]])
        assert err.value.code == 404
        with pytest.raises(ValueError, match="duplicate"):
            build_server([ModelEndpoint(str(tmp_path / "a")),
                          ModelEndpoint(str(tmp_path / "a"))], port=0)
    finally:
        server.shutdown()
        server.server_close()


def test_hot_reload_under_concurrent_load(tmp_path):
    """Hammer :predict from N threads while new versions export
    concurrently: every response must be valid and correspond to SOME
    exported version (the atomic (model, dtypes) swap under the reload
    lock must never produce a torn or failed response)."""
    import json as _json
    import threading
    import urllib.request

    from elasticdl_tpu.serving.export import export_servable
    from elasticdl_tpu.serving.server import ModelEndpoint, build_server

    base = str(tmp_path / "m")
    scales = {v: float(v) for v in range(1, 6)}

    def put(version):
        export_servable(
            os.path.join(base, str(version)),
            lambda p, x: x * p["s"],
            {"s": np.float32(scales[version])},
            np.zeros((1, 2), np.float32),
            model_name="hot", version=version, platforms=("cpu",))

    put(1)
    server = build_server(
        ModelEndpoint(base, poll_interval=0.01), port=0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    url = "http://127.0.0.1:%d/v1/models/hot:predict" % port
    stop = threading.Event()
    failures = []
    seen_scales = set()

    def hammer():
        while not stop.is_set():
            try:
                req = urllib.request.Request(
                    url, data=_json.dumps(
                        {"instances": [[1.0, 1.0]]}).encode())
                with urllib.request.urlopen(req, timeout=30) as resp:
                    out = _json.loads(resp.read())["predictions"]
                scale = out[0][0]
                if out[0] != [scale, scale] or (
                    scale not in scales.values()
                ):
                    failures.append(out)
                seen_scales.add(scale)
            except Exception as e:  # noqa: BLE001
                failures.append(repr(e))

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    try:
        for t in threads:
            t.start()
        for version in range(2, 6):
            put(version)
            import time as _time

            _time.sleep(0.3)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        server.shutdown()
        server.server_close()
    assert not failures, failures[:5]
    assert 5.0 in seen_scales  # the last version was eventually served
    assert len(seen_scales) >= 2  # at least one live flip observed
