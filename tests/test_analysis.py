"""Analysis tooling: access matrices (Fig 5), δ-model, schedule stats,
input-spec construction for every dry-run cell."""

import jax
import numpy as np
import pytest

from repro.configs import all_arch_ids, get_config
from repro.configs.shapes import SHAPES, applicable_shapes
from repro.core.access_matrix import access_matrix, locality_fraction
from repro.core.delta_model import (
    DEVICE_COST_PARAMS,
    PLANNING_TARGET,
    device_cost_params,
    fit_delta_model,
)
from repro.dist.sharding import Rules
from repro.graphs.formats import build_stripe_schedule
from repro.graphs.generators import make_graph
from repro.graphs.partition import balanced_blocks
from repro.launch.specs import input_specs


class TestAccessMatrix:
    def test_web_is_diagonal_kron_is_diffuse(self):
        web = make_graph("web", scale=12, efactor=8, kind="unit")
        kron = make_graph("kron", scale=12, efactor=8, kind="unit")
        P = 16
        loc_web = locality_fraction(access_matrix(web, balanced_blocks(web, P)))
        loc_kron = locality_fraction(access_matrix(kron, balanced_blocks(kron, P)))
        assert loc_web > 0.5 > loc_kron  # the paper's Fig-5 contrast

    def test_matrix_sums_to_edge_count(self):
        g = make_graph("twitter", scale=10, efactor=8, kind="unit")
        mat = access_matrix(g, balanced_blocks(g, 8))
        assert mat.sum() == g.nnz


class TestDeltaModel:
    def setup_method(self):
        self.g = make_graph("twitter", scale=11, efactor=8, kind="pagerank")

    def test_rounds_interpolates_monotonically(self):
        m = fit_delta_model(self.g, 16, r_sync=20, r_async=12, delta_min=16)
        rs = [m.rounds(d) for d in (16, 64, 256, 1024, m.B)]
        assert rs[0] <= rs[-1]
        assert all(a <= b + 1e-9 for a, b in zip(rs, rs[1:]))
        assert abs(rs[-1] - 20) < 1e-6

    def test_locality_discounts_gain(self):
        diffuse = fit_delta_model(self.g, 16, 20, 12, delta_min=16)
        web = make_graph("web", scale=11, efactor=8, kind="pagerank")
        clustered = fit_delta_model(web, 16, 20, 12, delta_min=16)
        # clustered topology → smaller freshness gain at fine δ
        assert clustered.rounds(16) > diffuse.rounds(16)

    def test_cost_model_penalizes_fine_delta(self):
        m = fit_delta_model(self.g, 16, 20, 12, delta_min=16)
        assert m.round_cost_s(16) > m.round_cost_s(m.B)

    def test_cost_params_follow_device_kind(self):
        from types import SimpleNamespace as Dev

        v5e = device_cost_params(Dev(platform="tpu", device_kind="TPU v5 lite"))
        assert (v5e.peak_flops, v5e.hbm_bw) == (197e12, 819e9)
        cpu = device_cost_params(Dev(platform="cpu", device_kind="cpu"))
        assert cpu == DEVICE_COST_PARAMS[PLANNING_TARGET]
        with pytest.raises(ValueError, match="TPU v9"):
            device_cost_params(Dev(platform="tpu", device_kind="TPU v9"))

    def test_best_delta_in_grid(self):
        m = fit_delta_model(self.g, 16, 20, 12, delta_min=16)
        grid = [64, 256, 1024]
        assert m.best_delta(grid) in {min(d, m.B) for d in grid}


class TestStripeScheduleStats:
    def test_flush_accounting_formulae(self):
        g = make_graph("urand", scale=10, efactor=8, kind="pagerank")
        sched = build_stripe_schedule(g, balanced_blocks(g, 8), 64, np.float32(0))
        assert sched.flushes_per_round == sched.S
        assert sched.flush_bytes_per_round() == sched.S * 8 * 64 * 4
        assert sched.padding_overhead >= 1.0


class TestInputSpecs:
    @pytest.mark.parametrize("arch", all_arch_ids())
    def test_all_cells_have_wellformed_specs(self, arch):
        cfg = get_config(arch)
        rules = Rules.default()
        for shape_name in applicable_shapes(cfg.family):
            shape = SHAPES[shape_name]
            kind, arg_specs, arg_shards = input_specs(cfg, shape, rules)
            assert kind == shape.kind
            flat_specs = jax.tree_util.tree_flatten(
                arg_specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)
            )[0]
            assert all(isinstance(s, jax.ShapeDtypeStruct) for s in flat_specs)
            # spec/shard trees must be congruent
            flat_shards = jax.tree_util.tree_flatten(
                arg_shards,
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
            )[0]
            assert len(flat_shards) == len(flat_specs)
            if kind == "train":
                tok_key = "embeds" if cfg.family == "vlm" else "tokens"
                assert arg_specs[0][tok_key].shape[0] == shape.global_batch
