"""tests_tpu/ runs on a chip or fails: a skipped check reads as a pass."""

import pytest


@pytest.fixture(autouse=True)
def _needs_tpu():
    import jax

    if jax.default_backend() != "tpu":
        pytest.fail(f"tests_tpu/ needs a TPU; jax.default_backend() is "
                    f"{jax.default_backend()!r}", pytrace=False)
