"""The image API's scheduler names (the reference's A1111-mapped surface,
diffusers backend.py:100-168), one case per name: each is a program of its
own to compile. A module of its own, so that `--dist loadfile` places these
compiles beside the other costly modules and not behind them; the
Karras-spaced names are tests/test_diffusion_schedulers_karras.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("transformers")

from localai_tpu.models import latent_diffusion as ld  # noqa: E402
from tests.test_latent_diffusion import sd_dir  # noqa: E402,F401 — fixture reuse

SCHEDULERS = (
    "ddim", "pndm", "unipc", "euler", "euler_a", "dpmpp_2m", "heun", "lms",
    "dpm_2", "dpm_2_a", "dpmpp_sde", "dpmpp_2m_sde",
)


@pytest.fixture(scope="module")
def images(sd_dir):  # noqa: F811
    """scheduler name → the image of one fixed request, drawn twice, each
    name compiled once (jitted, as engine/image_engine.py serves it)."""
    cfg, params, tok = ld.load_pipeline(sd_dir)

    def ids(text):
        return jnp.asarray(tok(text, padding="max_length", max_length=77,
                               truncation=True)["input_ids"], jnp.int32)[None]

    cond, un, drawn = ids("a photo of a cat"), ids(""), {}

    def image(sched):
        if sched not in drawn:
            gen = jax.jit(lambda p, c, u, key: ld.generate(
                cfg, p, c, u, key, steps=4, height=64, width=64,
                scheduler=sched))
            drawn[sched] = tuple(
                np.asarray(gen(params, cond, un, jax.random.key(7)))
                for _ in range(2))
        return drawn[sched]

    return image


def check_image(drawn):
    img1, img2 = drawn
    assert img1.shape == (1, 64, 64, 3)
    assert np.isfinite(img1).all()
    assert 0.0 <= img1.min() and img1.max() <= 1.0
    np.testing.assert_array_equal(img1, img2)  # same seed → same image


@pytest.mark.parametrize("sched", SCHEDULERS)
def test_generate_shape_range_and_determinism(images, sched):
    check_image(images(sched))


def test_supported_names_resolve_and_others_are_refused():
    for name in ld.SUPPORTED_SCHEDULERS:
        assert ld.resolve_scheduler(name)[0] in ld.K_SCHEDULERS + ld.T_SCHEDULERS
    for bad in ("pndm-nope", "ddim_karras", "k_unipc"):
        with pytest.raises(ValueError):
            ld.resolve_scheduler(bad)
