"""The Karras-spaced scheduler names of the image API, under both spellings
(our "_karras" suffix and the reference's "k_" prefix): a case a name, each
a program of its own to compile. Beside tests/test_diffusion_schedulers.py
(the plain names) and not in it, so that the two halves of these compiles
are two workers' under `--dist loadfile`."""

import numpy as np
import pytest

pytest.importorskip("transformers")

from localai_tpu.models import latent_diffusion as ld  # noqa: E402
from tests.test_diffusion_schedulers import (  # noqa: E402,F401 — fixture reuse
    check_image,
    images,
    sd_dir,
)

KARRAS = (
    "dpmpp_2m_karras", "euler_a_karras", "lms_karras", "k_euler", "k_dpm_2",
    "k_dpm_2_a", "k_dpmpp_sde", "k_dpmpp_2m_sde",
)


@pytest.mark.parametrize("sched", KARRAS)
def test_generate_shape_range_and_determinism(images, sched):  # noqa: F811
    check_image(images(sched))


def test_karras_spacing_changes_the_trajectory(images):  # noqa: F811
    assert np.abs(images("euler")[0] - images("k_euler")[0]).max() > 0


@pytest.mark.parametrize("base", ld.K_SCHEDULERS)
def test_both_karras_spellings_are_one_scheduler(base):
    assert ld.resolve_scheduler(base) == (base, False)
    assert (ld.resolve_scheduler(f"k_{base}")
            == ld.resolve_scheduler(f"{base}_karras") == (base, True))
