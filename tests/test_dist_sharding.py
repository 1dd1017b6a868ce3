"""repro.dist.sharding: rules round-trip, logical() gating, param specs."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import all_arch_ids, get_config
from repro.dist.compat import make_mesh
from repro.dist.sharding import (
    Rules,
    current_rules,
    logical,
    tree_param_specs,
    use_rules,
)


class FakeMesh:
    """Production mesh axis sizes without needing 512 local devices."""

    def __init__(self, **shape):
        self.shape = shape
        self.axis_names = tuple(shape)


PROD = FakeMesh(pod=2, data=16, model=16)


class TestRules:
    def test_roundtrip(self):
        r = Rules.default(shard_cache_heads=True, seq_axis="model")
        assert Rules.from_dict(r.to_dict()) == r
        assert r.to_dict()["kv_heads"] == "model"
        assert Rules.default().mapping["cache_seq"] == "model"

    def test_spec_drops_nondividing_and_reused_axes(self):
        r = Rules.default()
        # vocab 100 not divisible by |model|=16 → replicated
        assert r.spec(("vocab", "embed_fsdp"), PROD, (100, 64)) == P(None, "data")
        # batch spans pod×data = 32
        assert r.spec(("batch", "seq"), PROD, (64, 128)) == P(("pod", "data"), None)
        assert r.spec(("batch", "seq"), PROD, (8, 128)) == P(None, None)

    def test_use_rules_scopes(self):
        assert current_rules() is None
        with use_rules(Rules.default()) as r:
            assert current_rules() is r
        assert current_rules() is None


class TestLogical:
    def test_noop_outside_mesh(self):
        x = jnp.ones((4, 8))
        assert logical(x, ("batch", "embed")) is x
        with use_rules(Rules.default()):
            # rules active but still no mesh context → still a no-op
            assert logical(x, ("batch", "embed")) is x

    def test_applies_constraint_under_mesh(self):
        mesh = make_mesh((1, 1), ("data", "model"))
        x = jnp.ones((4, 8))
        with use_rules(Rules.default(seq_axis="model")), jax.set_mesh(mesh):
            y = jax.jit(lambda a: logical(a, ("batch", "embed")))(x)
        assert jnp.array_equal(y, x)


class TestTreeParamSpecs:
    @pytest.mark.parametrize("arch", all_arch_ids())
    def test_specs_valid_for_arch(self, arch):
        cfg = get_config(arch)
        from repro.models import init_params

        params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
        rules = Rules.default(seq_axis="model")
        specs = tree_param_specs(params, rules, PROD)
        flat_p, tdef_p = jax.tree_util.tree_flatten(params)
        flat_s, tdef_s = jax.tree_util.tree_flatten(
            specs, is_leaf=lambda x: isinstance(x, P)
        )
        assert tdef_p == tdef_s  # congruent trees
        for leaf, spec in zip(flat_p, flat_s):
            assert isinstance(spec, P)
            assert len(spec) == leaf.ndim
            used = []
            for dim, entry in zip(leaf.shape, spec):
                axes = entry if isinstance(entry, tuple) else (entry,)
                for a in axes:
                    if a is None:
                        continue
                    assert a in PROD.shape and a not in used
                    used.append(a)
                total = 1
                for a in axes:
                    if a is not None:
                        total *= PROD.shape[a]
                assert dim % total == 0

    def test_known_layouts(self):
        cfg = get_config("granite-8b")
        from repro.models import init_params

        params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
        specs = tree_param_specs(params, Rules.default(), PROD)
        assert specs["embed"] == P("model", "data")  # vocab × d_model
        layer = specs["layers"]["b0_attn"]
        assert layer["wq"] == P(None, "data", "model")  # stacked (L, d, H·hd)
        assert layer["wo"] == P(None, "model", "data")
        assert layer["ln1"] == P(None, None)
