"""TransferGateway discipline + input-spec construction tests."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bridge_opt import StagingArena
from repro.core.bridge import B300, TPU_V5E, BridgeModel
from repro.core.gateway import TransferGateway
from repro.core.policy import cc_aware_defaults
from repro.configs.base import SHAPES, get_config
from repro.obs import wall


class TestGateway:
    def _gw(self, cc_on, batching=None):
        defaults = cc_aware_defaults(cc_on)
        if batching is not None:
            defaults = dataclasses.replace(defaults,
                                           batch_small_crossings=batching)
        return TransferGateway(BridgeModel(B300, cc_on=cc_on), defaults,
                               pool_workers=1)

    def test_transfers_are_real(self):
        gw = self._gw(True)
        x = np.arange(12, dtype=np.float32)
        dev = gw.h2d(x)
        np.testing.assert_array_equal(np.asarray(dev), x)
        back = gw.d2h(dev)
        np.testing.assert_array_equal(back, x)

    def test_batching_pays_one_toll(self):
        arrays = [np.zeros(16, np.int32) for _ in range(8)]
        batched = self._gw(True, batching=True)
        unbatched = self._gw(True, batching=False)
        batched.batch_h2d(arrays)
        unbatched.batch_h2d(arrays)
        # 8 fresh crossings vs 1 registered crossing: >> 8x time difference
        assert unbatched.clock.now > 5 * batched.clock.now
        assert batched.stats.batched_crossings_saved == 7

    @pytest.mark.parametrize("arena", [False, True])
    def test_pricing_half_prices_as_batch_h2d(self, monkeypatch, arena):
        """``price_batch_h2d`` leaves the records, clock and stats that
        ``batch_h2d`` leaves over the same arrays, and moves nothing."""
        monkeypatch.setenv("REPRO_OBS", "1")
        wall.refresh()
        arrays = [np.arange(3, dtype=np.int32).reshape(3, 1),
                  np.arange(3, dtype=np.int32)] + [
            np.zeros(3, np.int32) for _ in range(4)]
        moved, priced = self._gw(True), self._gw(True)
        if arena:
            moved.arena = StagingArena(1 << 20)
            priced.arena = StagingArena(1 << 20)
        wall.clear()
        for _ in range(2):      # first touch, then warm
            moved.batch_h2d(arrays, op_class="prep_batched_h2d")
        assert [r.name for r in wall.records()].count("bridge.h2d") == 12
        wall.clear()
        for _ in range(2):
            priced.price_batch_h2d(arrays, op_class="prep_batched_h2d")
        names = [r.name for r in wall.records()]
        monkeypatch.undo()
        wall.refresh()
        wall.clear()
        assert "bridge.h2d" not in names and "account" in names
        assert priced.records == moved.records and len(priced.records) == 2
        assert priced.clock.now == moved.clock.now > 0
        assert dataclasses.asdict(priced.stats) == dataclasses.asdict(
            moved.stats)
        assert priced.stats.batched_crossings_saved == 10

    def test_fresh_staging_registers_on_reuse(self):
        gw = self._gw(True)
        x = np.zeros(64, np.float32)
        gw.h2d(x, reuse_staging=True)    # first touch: FRESH
        t1 = gw.clock.now
        gw.h2d(x, reuse_staging=True)    # warm: REGISTERED
        t2 = gw.clock.now - t1
        assert t2 < t1 / 10

    def test_accounting_records_per_crossing(self):
        gw = self._gw(True)
        gw.h2d(np.zeros(8, np.int32), op_class="alloc_h2d", reuse_staging=False)
        gw.d2h(jnp.zeros(8, jnp.int32), op_class="drain")
        classes = {r.op_class for r in gw.records}
        assert classes == {"alloc_h2d", "drain"}
        assert gw.stats.h2d_crossings == 1 and gw.stats.d2h_crossings == 1


class TestInputSpecs:
    """Input specs must be allocation-free and cover every model input."""

    def test_specs_are_abstract(self):
        from repro.launch.mesh import make_host_mesh
        from repro.launch import specs as specs_lib
        mesh = make_host_mesh()
        for arch in ("olmo-1b", "internvl2-76b", "seamless-m4t-medium",
                     "deepseek-v2-lite-16b", "hymba-1.5b"):
            cfg = get_config(arch)
            for shape_name in ("train_4k", "prefill_32k"):
                s = specs_lib.input_specs(cfg, SHAPES[shape_name], mesh)
                for leaf in jax.tree.leaves(s):
                    assert isinstance(leaf, jax.ShapeDtypeStruct)
            # frontends present exactly when the arch has one
            train = specs_lib.input_specs(cfg, SHAPES["train_4k"], mesh)
            assert ("patch_embeds" in train) == (cfg.family == "vlm")
            assert ("frames" in train) == (cfg.encoder_layers > 0)

    def test_decode_specs_include_cache_and_index(self):
        from repro.launch.mesh import make_host_mesh
        from repro.launch import specs as specs_lib
        mesh = make_host_mesh()
        cfg = get_config("olmo-1b")
        d = specs_lib.input_specs(cfg, SHAPES["decode_32k"], mesh)
        assert set(d) == {"caches", "tokens", "index"}
        assert d["tokens"].shape == (128, 1)
        kv_leaves = [l for l in jax.tree.leaves(d["caches"]) if l.ndim >= 4]
        assert kv_leaves and all(l.shape[2] == 32768 for l in kv_leaves)
