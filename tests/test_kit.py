"""``tests/decoder_kit.py``'s cache, held to its two conditions on a toy
kernel (no model): an artifact is a pure function of its key, computed under
the patches the key names inside the kit's OWN ``with``; and what a test may
mutate comes back in a fresh container."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_kit
from decoder_kit import HIGHEST, Kit, frozen

# a kernel module in small: a jitted function whose trace holds the `_mm` it was traced with
toy = types.SimpleNamespace(_mm=lambda a, b, dims=None: jnp.dot(a, b))
toy.kernel = jax.jit(lambda a, b: toy._mm(a, b))


def _operands():
    rng = np.random.default_rng(0)
    return [jnp.asarray(rng.standard_normal((32, 32)), jnp.bfloat16) for _ in range(2)]


def _kit():
    return Kit(patches={"float32_products": lambda: decoder_kit.float32_products(toy, toy.kernel)})


def test_the_same_key_returns_equal_arrays_in_a_container_of_its_own():
    kit, calls = _kit(), []

    def make():
        calls.append(1)
        return {"layers": [{"w": jnp.arange(4.0)}], "head": jnp.ones(2)}

    first = kit.made("params", 5, make, None)
    first["layers"] = [{**p, "w": p["w"] * 3.0} for p in first["layers"]]  # as a test rebinds a layer
    first["head"] = None
    again = kit.made("params", 5, make, None)
    assert len(calls) == 1 and again["layers"] is not first["layers"]
    np.testing.assert_array_equal(np.asarray(again["layers"][0]["w"]), np.arange(4.0))
    np.testing.assert_array_equal(np.asarray(again["head"]), np.ones(2))


def test_a_patch_is_part_of_the_key_and_does_not_leak_through_the_cache():
    kit = _kit()
    a, b = _operands()
    with jax.default_matmul_precision("highest"):
        assert toy.kernel(a, b).dtype == jnp.bfloat16  # a trace from before the patch: dropped on the way in
    patched = kit.made("product", 0, lambda: toy.kernel(a, b), (HIGHEST, "float32_products"))
    plain = kit.made("product", 0, lambda: toy.kernel(a, b), (HIGHEST,))
    assert patched.dtype == jnp.float32 and plain.dtype == jnp.bfloat16  # two entries, each under its own
    toy.kernel.clear_cache()
    fresh = toy.kernel(a, b)  # uncached, unpatched, AFTER the patched one was cached
    assert fresh.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(plain, np.float32), np.asarray(fresh, np.float32))
    assert not decoder_kit.ACTIVE and toy._mm(a, b).dtype == jnp.bfloat16  # and nothing stays patched in


def test_an_artifact_is_computed_under_the_kit_s_own_patches_whatever_the_asker_holds():
    kit = _kit()
    a, b = _operands()
    with jax.default_matmul_precision("highest"):  # the asker's: the key names none
        assert kit.made("precision", 0, lambda: jax.config.jax_default_matmul_precision, ()) is None
    assert kit.made("precision", 1, lambda: jax.config.jax_default_matmul_precision, (HIGHEST,)) == "highest"
    with decoder_kit.float32_products(toy, toy.kernel):  # a fixture the key does not name: refused
        with pytest.raises(AssertionError, match="float32_products"):
            kit.made("product", 1, lambda: toy.kernel(a, b), (HIGHEST,))
        held = kit.made("product", 1, lambda: toy.kernel(a, b), (HIGHEST, "float32_products"))
    assert held.dtype == jnp.float32


def test_the_overrides_key_does_not_depend_on_their_order():
    kit, calls = _kit(), []
    one = dict(num_experts=4, experts_held=[0, 4], rope={"theta": 1.0, "factor": 2})
    other = dict(rope={"factor": 2, "theta": 1.0}, experts_held=[0, 4], num_experts=4)
    assert list(one) != list(other) and frozen(one) == frozen(other) and hash(frozen(one)) == hash(frozen(other))
    for over in (one, other):
        kit.made("trunk", (3, 1, over), lambda: calls.append(1), (HIGHEST,))
    assert len(calls) == 1
    kit.made("trunk", (3, 1, {**one, "num_experts": 8}), lambda: calls.append(1), (HIGHEST,))
    assert len(calls) == 2
