"""Distribution tests — run in subprocesses with forced host devices
(the main pytest process must keep the default single device)."""
import json
import subprocess
import sys
import textwrap

import pytest

def run_with_devices(n: int, code: str) -> dict:
    """Execute ``code`` under n forced host devices; code prints JSON."""
    prog = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={n}'\n"
        + textwrap.dedent(code)
    )
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env={**__import__('os').environ, "PYTHONPATH": "src"}, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


class TestShardedSNN:
    def test_sharded_matches_single_device(self):
        """Neuron-sharded shard_map engine == same engine on 1 device."""
        res = run_with_devices(8, """
        import jax, json
        import numpy as np
        from repro.core.distributed import build_sharded

        def totals(mesh_shape):
            mesh = jax.make_mesh(mesh_shape, ("model",))
            snn = build_sharded(mesh, "model", n_neurons=1024, fanin=32,
                                max_delay=8, seed=3)
            state, counts = snn.run(300)
            return np.asarray(counts)

        c8 = totals((8,))
        c1 = totals((1,))
        # same network, same per-device-fold RNG differs for generators ->
        # compare dynamics statistically, not bitwise
        ok = (abs(int(c8.sum()) - int(c1.sum())) / max(int(c1.sum()), 1)) < 0.2
        print(json.dumps({"sum8": int(c8.sum()), "sum1": int(c1.sum()),
                          "ok": bool(ok)}))
        """)
        assert res["ok"], res

    @pytest.mark.slow
    def test_dp_tp_lm_matches_single_device(self):
        """jit+GSPMD training step on a 2x2 mesh == single-device step."""
        res = run_with_devices(4, """
        import jax, json
        import numpy as np
        from repro.configs import get_arch, reduce_arch
        from repro.models import tasks
        from repro.optim.adamw import AdamWConfig
        from repro.precision import get_policy
        from repro.data.synthetic import TokenStream
        from repro.launch.mesh import make_host_mesh

        cfg = reduce_arch(get_arch("smollm-360m"))
        policy = get_policy("fp16")
        opt = AdamWConfig(lr=1e-3)
        stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=32,
                             global_batch=4, seed=0)
        batch = stream.batch(0)

        # single device
        s1 = tasks.init_train_state(cfg, policy, seed=0, opt_cfg=opt)
        f1 = jax.jit(tasks.make_train_step(cfg, policy, opt_cfg=opt,
                                           ce_chunk=32))
        _, m1 = f1(s1, batch)

        # 2x2 mesh via build_task shardings
        mesh = make_host_mesh((2, 2), ("data", "model"))
        from repro.configs.base import ShapeConfig
        shape = ShapeConfig("tiny", 32, 4, "train")
        task = tasks.build_task(cfg, shape, mesh, policy, seq_shard=False,
                                ce_chunk=32)
        s2 = tasks.init_train_state(cfg, policy, seed=0, opt_cfg=opt)
        _, m2 = task.jitted()(s2, batch)

        l1, l2 = float(m1["loss"]), float(m2["loss"])
        print(json.dumps({"l1": l1, "l2": l2,
                          "ok": bool(abs(l1 - l2) / l1 < 1e-3)}))
        """)
        assert res["ok"], res

    def test_compressed_psum_close_to_exact(self):
        res = run_with_devices(4, """
        import jax, json
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim.compress import psum_compressed

        mesh = jax.make_mesh((4,), ("pod",))

        def reduce_with(method):
            def f(x):
                return psum_compressed(x, "pod", method)
            return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                                         out_specs=P("pod")))

        x = jax.random.normal(jax.random.key(0), (4, 64), jnp.float32)
        exact = np.asarray(reduce_with(None)(x))
        bf16 = np.asarray(reduce_with("bf16")(x))
        int8 = np.asarray(reduce_with("int8")(x))
        e_bf = float(np.abs(bf16 - exact).max())
        e_i8 = float(np.abs(int8 - exact).max())
        scale = float(np.abs(exact).max())
        print(json.dumps({"e_bf": e_bf, "e_i8": e_i8,
                          "ok": bool(e_bf < 0.02 * scale and
                                     e_i8 < 0.05 * scale)}))
        """)
        assert res["ok"], res

    def test_elastic_reshard_8_to_4(self):
        """Fault tolerance: state sharded on 8 devices re-lays onto 4."""
        res = run_with_devices(8, """
        import jax, json
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.checkpoint.ckpt import reshard

        x = jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8)
        m8 = jax.make_mesh((8,), ("model",))
        m4 = jax.make_mesh((4,), ("model",), devices=jax.devices()[:4])
        x8 = jax.device_put(x, NamedSharding(m8, P("model", None)))
        x4 = reshard(x8, NamedSharding(m4, P("model", None)))
        ok = (np.array_equal(np.asarray(x4), np.asarray(x))
              and len(x4.sharding.device_set) == 4)
        print(json.dumps({"ok": bool(ok)}))
        """)
        assert res["ok"], res


class TestElasticTraining:
    @pytest.mark.slow
    def test_elastic_train_8_to_4_devices(self):
        """End-to-end elasticity: train sharded on a 4x2 mesh, checkpoint,
        lose half the devices, re-shard onto 2x2, keep training — loss
        stream stays finite and descending."""
        res = run_with_devices(8, """
        import jax, json
        import numpy as np
        from jax.sharding import NamedSharding
        from repro.configs import get_arch, reduce_arch
        from repro.configs.base import ShapeConfig
        from repro.models import tasks
        from repro.optim.adamw import AdamWConfig
        from repro.precision import get_policy
        from repro.data.synthetic import TokenStream

        cfg = reduce_arch(get_arch("smollm-360m"))
        policy = get_policy("fp16")
        opt = AdamWConfig(lr=3e-3)
        shape = ShapeConfig("tiny", 32, 4, "train")
        stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=32,
                             global_batch=4, seed=0)

        mesh8 = jax.make_mesh((4, 2), ("data", "model"))
        task8 = tasks.build_task(cfg, shape, mesh8, policy, seq_shard=False,
                                 ce_chunk=32)
        state = tasks.init_train_state(cfg, policy, seed=0, opt_cfg=opt)
        step8 = task8.jitted()
        losses = []
        for i in range(3):
            state, m = step8(state, stream.batch(i))
            losses.append(float(m["loss"]))

        # "pod loss": re-shard onto the surviving 4 devices
        mesh4 = jax.make_mesh((2, 2), ("data", "model"),
                              devices=jax.devices()[:4])
        task4 = tasks.build_task(cfg, shape, mesh4, policy, seq_shard=False,
                                 ce_chunk=32)
        from repro.checkpoint.ckpt import reshard
        state4 = reshard(jax.device_get(state), task4.in_shardings[0])
        step4 = task4.jitted()
        for i in range(3, 6):
            state4, m = step4(state4, stream.batch(i))
            losses.append(float(m["loss"]))

        ok = (all(np.isfinite(losses))
              and np.mean(losses[3:]) < np.mean(losses[:3]) + 0.5)
        print(json.dumps({"losses": losses, "ok": bool(ok)}))
        """)
        assert res["ok"], res
