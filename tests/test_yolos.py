"""YOLOS detection: HF checkpoint round-trip parity against torch (VERDICT
r2 item 9b — real published detector architecture must load and match)."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
from localai_tpu.models import yolos as Y  # noqa: E402


@pytest.fixture(scope="module")
def tiny_ckpt(tmp_path_factory):
    # imported here and not at the top: `transformers`' model classes pull in
    # TensorFlow, 7 s that every worker of the run would pay at collection
    from transformers import YolosConfig as HFYolosConfig
    from transformers import YolosForObjectDetection

    d = tmp_path_factory.mktemp("yolos")
    cfg = HFYolosConfig(
        hidden_size=32, num_hidden_layers=2, num_attention_heads=2,
        intermediate_size=64, image_size=[64, 96], patch_size=16,
        num_detection_tokens=5, num_labels=91,
        id2label={i: f"c{i}" for i in range(91)},
        label2id={f"c{i}": i for i in range(91)},
    )
    torch.manual_seed(0)
    model = YolosForObjectDetection(cfg)
    model.eval()
    model.save_pretrained(str(d), safe_serialization=True)
    return str(d), model


def test_yolos_matches_torch(tiny_ckpt):
    ckpt_dir, model = tiny_ckpt
    assert Y.is_yolos_dir(ckpt_dir)
    cfg, params = Y.load_yolos(ckpt_dir)
    assert (cfg.image_height, cfg.image_width) == (64, 96)
    assert cfg.num_labels == 91 and cfg.id2label[3] == "c3"

    rng = np.random.default_rng(0)
    pixels = rng.normal(size=(1, 3, 64, 96)).astype(np.float32)
    logits, boxes = Y.forward(cfg, params, jnp.asarray(pixels))
    with torch.no_grad():
        out = model(pixel_values=torch.tensor(pixels))
    assert np.allclose(np.asarray(logits), out.logits.numpy(), atol=2e-4), float(
        np.abs(np.asarray(logits) - out.logits.numpy()).max()
    )
    assert np.allclose(np.asarray(boxes), out.pred_boxes.numpy(), atol=2e-4)


def test_yolos_serves_through_manager(tiny_ckpt, tmp_path):
    import yaml

    from localai_tpu.config import ApplicationConfig
    from localai_tpu.engine.image_engine import YolosEngine
    from localai_tpu.server import ModelManager

    ckpt_dir, _ = tiny_ckpt
    (tmp_path / "det.yaml").write_text(yaml.safe_dump({
        "name": "det", "backend": "detection", "model": ckpt_dir,
    }))
    manager = ModelManager(ApplicationConfig(models_dir=str(tmp_path)))
    try:
        lm = manager.get("det")
        assert isinstance(lm.engine, YolosEngine)
        img = (np.random.default_rng(1).random((100, 160, 3)) * 255).astype(np.uint8)
        dets = lm.engine.detect(img, threshold=0.0)
        assert isinstance(dets, list)
        for d in dets:
            assert 0.0 <= d["confidence"] <= 1.0
            assert 0.0 <= d["x"] <= 160 and 0.0 <= d["y"] <= 100
            assert d["class_name"].startswith("c")
    finally:
        manager.shutdown()
