"""Paper §3.3 / Alg 1: parallel loading overlap.

Materializes image batch files on disk and compares steps/s of training with
the background ParallelLoader vs the synchronous in-loop loader. Derived:
overlap efficiency (parallel/sync throughput; >1 means the loader hid IO).
"""
import tempfile
import time

from repro.launch.mesh import make_mesh


def run():
    import jax
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.core import get_exchanger, init_train_state, make_bsp_step
    from repro.data.prefetch import ParallelLoader, SyncLoader
    from repro.data.synthetic import ImageSource, materialize_batch_files
    from repro.models import build_model
    from repro.optim import constant, sgd_momentum

    cfg = get_smoke_config("alexnet")
    model = build_model(cfg)
    opt = sgd_momentum(weight_decay=0.0)
    mesh = make_mesh((1,), ("data",))
    jax.set_mesh(mesh)
    step = jax.jit(make_bsp_step(model, opt, get_exchanger("ar"),
                                 constant(0.01), mesh))
    n_batches, bsz = 16, 16
    with tempfile.TemporaryDirectory() as td:
        src = ImageSource(cfg.image_size, cfg.num_classes)
        files = materialize_batch_files(src, td, n_batches, bsz)
        mean = np.zeros((cfg.image_size, cfg.image_size, 3), np.float32)
        rows = []
        # local disk (IO << compute) and simulated remote disk (IO ~ compute,
        # the paper's motivating case: "network bandwidth if reading from
        # remote disks")
        for name, loader_cls, kw in [
                ("sync_local", SyncLoader, {}),
                ("parallel_local", ParallelLoader, {"depth": 3}),
                ("sync_remote", SyncLoader, {"io_delay_ms": 400}),
                ("parallel_remote", ParallelLoader,
                 {"depth": 3, "io_delay_ms": 400})]:
            loader = loader_cls(files, image_mean=mean,
                                crop=cfg.image_size - 8, **kw)
            state = init_train_state(model, opt, jax.random.key(0))
            it = iter(loader)
            b = next(it)
            state, _ = step(state, b, jax.random.key(0))  # compile
            jax.block_until_ready(state)
            t0 = time.perf_counter()
            n = 0
            for b in it:
                state, _ = step(state, b, jax.random.key(n))
                n += 1
            jax.block_until_ready(state)
            dt = time.perf_counter() - t0
            rows.append((name, dt / max(n, 1) * 1e6, n / dt))
            if hasattr(loader, "stop"):
                loader.stop()
    base = {"local": rows[0][2], "remote": rows[2][2]}
    return [(f"loading/{name}", us, f"steps_per_s={sps:.2f};"
             f"speedup_vs_sync={sps / base[name.split('_')[1]]:.2f}")
            for name, us, sps in rows]


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
