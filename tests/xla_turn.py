"""The latent query's rotary part as XLA turned it until PR 61, kept as the
tests' oracle for the turn ``sparse_attention._causal_kernel`` makes since
(``tests/test_decoder_kimi.py``, ``tests/test_decoder_dsv32.py``)."""

import jax.numpy as jnp
import numpy as np
import pytest

from psana_ray_tpu.models import decoder
from psana_ray_tpu.parallel import sparse_attention as sa


def turned_by_xla(raw, scalar, heads):
    """The shared query part ``raw [B, S, H*ds]`` as ``_latent_projections``
    turned it until PR 61 (:func:`decoder.rotate`, then the softmax-and-YaRN
    ``scalar``, one rounding to ``raw``'s type), and the two tables the
    kernel turns it by since: ``(turned [B, S, H*ds], (cos, sin))``."""
    b, s, width = raw.shape
    angles = jnp.tile(decoder.rotary_angles(np.arange(s), 10000.0, width // heads // 2), (b, 1))
    turned = decoder.rotate(raw.astype(jnp.float32).reshape(b * s, heads, -1), angles) * scalar
    return turned.reshape(b, s, width).astype(raw.dtype), decoder.turn_tables(angles)


def assert_the_kernel_s_turn_is_xla_s(in_kernel, by_xla, raw, turned, tables, scalar, heads, atol):
    """The output under the turn in the kernel against the output given the
    turned query ``turned``: within ``atol``, and equal to the bit where the
    turned tiles are (the kernel's arithmetic on the whole array, outside
    it) and are ROUNDED, to bf16 as the cells' are. A float32 tile is never
    rounded, and there XLA's CPU backend contracts ``x*c + h*s`` inside the
    interpreted kernel's one compiled body where op by op nothing is
    contracted: the last bit of a turned component, 6e-7 on the output."""
    b, s, width = raw.shape
    tile = sa._turned_tile(jnp.transpose(raw.reshape(b * s, heads, -1), (1, 0, 2)), *tables, scalar,
                           turned.dtype)
    same = np.array_equal(np.asarray(tile.reshape(heads, b * s, -1), np.float32), np.asarray(
        jnp.transpose(turned.reshape(b * s, heads, -1), (1, 0, 2)), np.float32))
    if same and turned.dtype != jnp.float32:
        np.testing.assert_array_equal(np.asarray(in_kernel, np.float32), np.asarray(by_xla, np.float32))
    np.testing.assert_allclose(np.asarray(in_kernel, np.float32), np.asarray(by_xla, np.float32),
                               atol=atol)
    return same


# the rotary query turned by XLA before the kernel (None: as until PR 61, and as every caller
# without the tables still has it), or float32 and unturned with its tables, the kernel turning
# its tile once a query tile: without and with YaRN's scalar on it
TURNS = pytest.mark.parametrize("turn", [None, 1.0, 0.1147],
                                ids=["turned_by_xla", "in_kernel", "in_kernel_yarn_scalar"])
