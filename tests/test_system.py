"""End-to-end behaviour tests for the paper's system.

1. The three execution disciplines (sync / async / delayed-δ) agree on the
   answer and differ only in rounds + commit traffic (the paper's thesis).
2. δ monotonically trades flush traffic against freshness.
3. The full training driver runs: data → model → optimizer → checkpoint →
   injected failure → restart → final loss improvement.
4. The serving driver generates greedy tokens from prefill + decode.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.algorithms import pagerank, sssp
from repro.graphs.generators import make_graph


class TestPaperThesis:
    def setup_method(self):
        self.g = make_graph("twitter", scale=11, efactor=8, kind="pagerank")

    def test_same_answer_different_schedule(self):
        rs = pagerank(self.g, P=8, delta="sync")
        ra = pagerank(self.g, P=8, delta="async", min_chunk=16)
        rd = pagerank(self.g, P=8, delta=256, min_chunk=16)
        assert np.abs(rs.x - ra.x).max() < 5e-5
        assert np.abs(rs.x - rd.x).max() < 5e-5

    def test_async_fewer_rounds_on_diffuse_graph(self):
        """Paper Table I direction: sharing sooner converges in fewer rounds."""
        rs = pagerank(self.g, P=8, delta="sync")
        ra = pagerank(self.g, P=8, delta="async", min_chunk=16)
        assert ra.rounds < rs.rounds

    def test_delta_interpolates_rounds(self):
        """Hybrid rounds sit between sync and async (freshness monotonicity)."""
        rs = pagerank(self.g, P=8, delta="sync")
        ra = pagerank(self.g, P=8, delta="async", min_chunk=16)
        rd = pagerank(self.g, P=8, delta=512, min_chunk=16)
        assert ra.rounds <= rd.rounds <= rs.rounds

    def test_delta_reduces_flushes_vs_async(self):
        """The hybrid's whole point: fewer commit collectives than async."""
        ra = pagerank(self.g, P=8, delta="async", min_chunk=16)
        rd = pagerank(self.g, P=8, delta=512, min_chunk=16)
        assert rd.flushes / rd.rounds < (ra.flushes / ra.rounds) / 4

    def test_sssp_all_modes_exact(self):
        g = make_graph("twitter", scale=10, efactor=8, kind="sssp")
        rs = sssp(g, P=8, delta="sync")
        ra = sssp(g, P=8, delta="async", min_chunk=16)
        rd = sssp(g, P=8, delta=128, min_chunk=16)
        assert (rs.x == ra.x).all() and (rs.x == rd.x).all()


class TestSharded:
    def test_sharded_engine_matches_reference(self):
        """shard_map worker execution == single-device engine, bit-exact.

        Runs in a subprocess so the 4-device host platform doesn't leak into
        this test session (device count locks on first jax init).
        """
        code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.graphs.generators import make_graph
from repro.core.engine import make_schedule, round_fn
from repro.core.semiring import PLUS_TIMES
from repro.dist.compat import make_mesh
from repro.dist.engine_sharded import sharded_round_fn
g = make_graph("web", scale=10, efactor=8, kind="pagerank")
n = g.n; tele = np.float32((1-.85)/n)
sched = make_schedule(g, 4, 64, PLUS_TIMES, mode="delayed")
ru = lambda old, red, rows: tele + red
rnd = jax.jit(round_fn(sched, PLUS_TIMES, ru))
x0 = jnp.concatenate([jnp.full((n,), 1.0/n, jnp.float32), jnp.zeros((1,), jnp.float32)])
x_ref = rnd(rnd(x0))
mesh = make_mesh((4,), ("data",))
srnd = jax.jit(sharded_round_fn(sched, PLUS_TIMES, ru, mesh, axis="data"))
with jax.set_mesh(mesh):
    args = (sched.src, sched.val, sched.dst_local, sched.rows, sched.row_last)
    x_s = srnd(srnd(x0, *args), *args)
assert float(jnp.abs(x_ref - x_s).max()) == 0.0, "sharded != reference"
print("OK")
"""
        env = dict(os.environ, PYTHONPATH="src")
        r = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert "OK" in r.stdout, r.stderr[-2000:]


class TestDrivers:
    def test_train_driver_end_to_end(self, tmp_path):
        from repro.launch.train import main

        hist = main(
            [
                "--arch", "minicpm-2b", "--reduced", "--steps", "8",
                "--batch", "4", "--seq", "32", "--ckpt-every", "4",
                "--ckpt-dir", str(tmp_path), "--fail-at", "5",
            ]
        )
        assert hist["restarts"] == 1
        assert len(hist["loss"]) >= 8

    def test_train_driver_delayed_commit(self, tmp_path):
        from repro.launch.train import main

        hist = main(
            [
                "--arch", "granite-8b", "--reduced", "--steps", "6",
                "--batch", "4", "--seq", "32", "--commit-delta", "2",
                "--n-pods", "2", "--ckpt-dir", str(tmp_path),
            ]
        )
        assert len(hist["loss"]) >= 6

    def test_serve_driver(self):
        from repro.configs import get_reduced
        from repro.launch.serve import generate
        from repro.models import init_params

        cfg = get_reduced("recurrentgemma_9b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        prompts = np.zeros((2, 12), np.int32)
        toks = generate(cfg, params, prompts, gen_len=6)
        assert toks.shape == (2, 6)
        assert (np.asarray(toks) >= 0).all() and (np.asarray(toks) < cfg.vocab).all()
