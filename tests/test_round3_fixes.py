"""Round-3 hardening: run_scanned, BN batch-stat gradients,
executor feed/donation aliasing, hard-example positive demotion."""
import logging

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt


def _tiny_train_program(B=4, D=8):
    main_p, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup):
        with pt.unique_name.guard():
            x = pt.layers.data("x", (D,), dtype="float32")
            y = pt.layers.data("y", (1,), dtype="float32")
            pred = pt.layers.fc(x, size=1)
            loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
            pt.optimizer.SGD(0.1).minimize(loss)
    return main_p, startup, loss


class TestRunScanned:
    def _feeds(self, steps, B=4, D=8, seed=0):
        rng = np.random.RandomState(seed)
        return {"x": rng.rand(steps, B, D).astype("float32"),
                "y": rng.rand(steps, B, 1).astype("float32")}

    def test_zero_steps_ok(self):
        main_p, startup, loss = _tiny_train_program()
        exe = pt.Executor()
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe.run(startup)
            out = exe.run_scanned(main_p, feed=self._feeds(0),
                                  fetch_list=[loss])
        assert np.asarray(out[0]).shape == (0,)

    def test_run_after_scan_keeps_distinct_prng(self):
        """run() after run_scanned must re-seed its on-device counter
        from the advanced host step (no permanently lagging stream)."""
        main_p, startup, loss = _tiny_train_program()
        exe = pt.Executor()
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe.run(startup)
            dev = exe.place.jax_device()
            exe.run(main_p, feed={k: v[0] for k, v in
                                  self._feeds(1).items()},
                    fetch_list=[loss])
            assert dev in exe._step_counters
            exe.run_scanned(main_p, feed=self._feeds(3),
                            fetch_list=[loss])
            # counter dropped: next run() re-seeds from self._step
            assert dev not in exe._step_counters
            host_step = exe._step
            exe.run(main_p, feed={k: v[0] for k, v in
                                  self._feeds(1).items()},
                    fetch_list=[loss])
            assert int(exe._step_counters[dev]) == host_step + 1


class TestFeedAliasing:
    def test_fed_persist_buffer_is_copied(self):
        """Feeding the exact jax.Array that lives in the scope as a
        persistable must not be invalidated by donation."""
        main_p, startup = pt.Program(), pt.Program()
        with pt.program_guard(main_p, startup):
            with pt.unique_name.guard():
                x = pt.layers.data("x", (4,), dtype="float32")
                w = pt.layers.create_parameter([4, 4], "float32",
                                               name="w_alias")
                out = pt.layers.reduce_sum(pt.layers.matmul(x, w))
        exe = pt.Executor()
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe.run(startup)
            wname = [v.name for v in main_p.persistable_vars()][0]
            wbuf = scope.get(wname)
            assert isinstance(wbuf, jax.Array)
            # feed the persistable buffer itself as x
            res = exe.run(main_p, feed={"x": wbuf[:1]},
                          fetch_list=[out])
            assert np.isfinite(res[0]).all()
            # the exact aliasing case: same object in feed and persist
            feeds = {"x": jnp.zeros((1, 4), jnp.float32)}
            fa = exe._put_feeds(main_p, feeds, exe.place.jax_device())
            persist = {wname: fa["x"]}
            exe._unalias_feeds(fa, persist)
            assert fa["x"] is not persist[wname]


class TestBf16AotRoundtrip:
    def test_save_load_compiled_bf16_params(self, tmp_path):
        """npz cannot hold bfloat16; save_compiled must view-cast and
        load_compiled must restore the true dtype bit-exactly."""
        from paddle_tpu.inference import InferenceEngine
        from paddle_tpu.models import mnist as mn
        main_p, startup = pt.Program(), pt.Program()
        with pt.program_guard(main_p, startup):
            with pt.unique_name.guard():
                img = pt.layers.data("image", (16,), dtype="float32")
                pred = pt.layers.fc(img, size=4)
        infer_p = main_p.clone(for_test=True)
        scope = pt.Scope()
        exe = pt.Executor()
        with pt.scope_guard(scope):
            exe.run(startup)
        eng = InferenceEngine(infer_p, ["image"], [pred], scope,
                              use_bf16=True)
        x = np.random.RandomState(0).rand(2, 16).astype("float32")
        ref = eng.run({"image": x})[0]
        d = str(tmp_path / "aot")
        eng.save_compiled(d, {"image": (2, 16)})
        loaded = InferenceEngine.load_compiled(d)
        for k, v in loaded._persist.items():
            assert v.dtype == eng._persist[k].dtype
        out = loaded.run({"image": x})[0]
        np.testing.assert_allclose(ref, out, rtol=1e-2, atol=1e-2)


class TestBatchNormStatGrads:
    def test_saved_stats_carry_gradients(self):
        """A loss that reads SavedMean/SavedVariance must push nonzero,
        analytically-correct gradients into x."""
        from paddle_tpu.ops.kernels_nn import _bn_train
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(6, 3, 4, 4).astype("float32"))
        scale = jnp.ones(3, jnp.float32)
        bias = jnp.zeros(3, jnp.float32)
        red = (0, 2, 3)
        sample = x[:1, :, :1, :1]

        def loss_via_stats(x):
            y, bm, bv = _bn_train(x, scale, bias, sample, red, 1e-5)
            return jnp.sum(bm ** 2) + jnp.sum(bv ** 2)

        def loss_ref(x):
            xf = x.astype(jnp.float32)
            bm = jnp.mean(xf, axis=red)
            bv = jnp.var(xf, axis=red)
            return jnp.sum(bm ** 2) + jnp.sum(bv ** 2)

        g = jax.grad(loss_via_stats)(x)
        g_ref = jax.grad(loss_ref)(x)
        assert float(jnp.max(jnp.abs(g))) > 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-5)

    def test_y_path_gradient_unchanged(self):
        from paddle_tpu.ops.kernels_nn import _bn_train
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(4, 2, 3, 3).astype("float32"))
        scale = jnp.asarray(rng.rand(2).astype("float32") + 0.5)
        bias = jnp.asarray(rng.rand(2).astype("float32"))
        red = (0, 2, 3)
        sample = x[:1, :, :1, :1]

        def loss(x, scale, bias):
            y, _, _ = _bn_train(x, scale, bias, sample, red, 1e-5)
            return jnp.sum(y ** 2)

        def loss_ref(x, scale, bias):
            xf = x.astype(jnp.float32)
            bm = jnp.mean(xf, axis=red, keepdims=True)
            bv = jnp.var(xf, axis=red, keepdims=True)
            y = (xf - bm) * jax.lax.rsqrt(bv + 1e-5) \
                * scale.reshape(1, -1, 1, 1) + bias.reshape(1, -1, 1, 1)
            return jnp.sum(y ** 2)

        g = jax.grad(loss, argnums=(0, 1, 2))(x, scale, bias)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(x, scale, bias)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)


class TestHardExampleMining:
    def _run(self, mining, **attrs):
        from paddle_tpu.ops.registry import get_kernel

        class Ctx:
            is_test = False
        cls_loss = jnp.asarray([[0.9, 0.1, 0.8, 0.2, 0.7, 0.05]],
                               jnp.float32)
        match = jnp.asarray([[0, -1, 1, -1, -1, -1]], jnp.int32)
        dist = jnp.asarray([[0.9, 0.1, 0.8, 0.2, 0.1, 0.05]],
                           jnp.float32)
        ins = {"ClsLoss": [cls_loss], "MatchIndices": [match],
               "MatchDist": [dist], "LocLoss": [cls_loss * 0.1]}
        a = {"mining_type": mining, "neg_pos_ratio": 1.0,
             "sample_size": 3, "neg_dist_threshold": 0.5}
        a.update(attrs)
        out = get_kernel("mine_hard_examples")(Ctx(), ins, a)
        return (np.asarray(out["NegIndices"][0]),
                np.asarray(out["UpdatedMatchIndices"][0]))

    def test_hard_example_demotes_unselected_positives(self):
        neg, upd = self._run("hard_example")
        # top-3 by cls+loc loss: priors 0 (0.99), 2 (0.88), 4 (0.77)
        # prior 0 and 2 are positives and selected -> kept
        assert upd[0, 0] == 0 and upd[0, 2] == 1
        # negatives in the selection: prior 4 only
        assert neg[0].tolist() == [0, 0, 0, 0, 1, 0]
        # no positive outside the selection in this config; shrink the
        # sample so positive prior 2 falls out and must be demoted
        neg2, upd2 = self._run("hard_example", sample_size=1)
        assert upd2[0, 0] == 0      # top-1 is prior 0 (selected, kept)
        assert upd2[0, 2] == -1     # positive not selected -> background

    def test_hard_example_rejects_nonpositive_sample_size(self):
        with pytest.raises(ValueError, match="sample_size"):
            self._run("hard_example", sample_size=0)

    def test_max_negative_keeps_positives(self):
        neg, upd = self._run("max_negative")
        assert upd[0].tolist() == [0, -1, 1, -1, -1, -1]
        # eligible negatives (match==-1, dist<0.5): 1,3,4,5; 2 positives
        # * ratio 1.0 -> 2 selected, highest loss: 4 (0.7), 3 (0.2)
        assert neg[0].tolist() == [0, 0, 0, 1, 1, 0]
