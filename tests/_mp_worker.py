"""Subprocess worker for multi-process eager collective tests.

The analog of running a reference test file under ``mpirun -np N``
(SURVEY §4): the same assertions, but rank/size/controller address come
from the launcher env. Exits 0 on success; any assertion error exits
non-zero and the parent test fails.
"""

import os
import sys

# Workers run on CPU with a single device each (one process == one rank,
# exactly the reference's process model): pin the platform via config
# before any backend starts — N worker processes must never contend for a
# real chip.
os.environ.pop("JAX_PLATFORMS", None)
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# With HOROVOD_TEST_JAX_COORD set, workers form a real multi-process JAX
# world (gloo-backed CPU collectives) so the eager XLA data plane runs the
# same cross-process compiled-collective path it uses on TPU pods.
_coord = os.environ.get("HOROVOD_TEST_JAX_COORD")
if _coord:
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        _coord,
        num_processes=int(os.environ["HOROVOD_SIZE"]),
        process_id=int(os.environ["HOROVOD_RANK"]))

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import horovod_tpu as hvd  # noqa: E402


def main() -> None:
    scenario = sys.argv[1]
    if scenario.startswith("subset"):
        return _subset_scenario(scenario)
    hvd.init()
    rank, size = hvd.rank(), hvd.size()
    assert size == int(os.environ["HOROVOD_SIZE"])
    assert rank == int(os.environ["HOROVOD_RANK"])

    if scenario == "allreduce":
        x = np.full((8, 4), float(rank + 1), dtype=np.float32)
        out = hvd.allreduce(x, average=False, name="mp.sum")
        expected = sum(range(1, size + 1))
        np.testing.assert_array_equal(np.asarray(out), expected)
        avg = hvd.allreduce(x, average=True, name="mp.avg")
        np.testing.assert_allclose(np.asarray(avg), expected / size)
        if isinstance(avg, np.ndarray):
            avg += 0.0  # results must be writable on every data plane
                        # (the torch front-end mutates them in place)

    elif scenario == "fused":
        tensors = [np.full((50,), float(rank + i), np.float32)
                   for i in range(10)]
        handles = [hvd.allreduce_async(t, average=False, name=f"mp.fused.{i}")
                   for i, t in enumerate(tensors)]
        for i, h in enumerate(handles):
            out = hvd.synchronize(h)
            expected = sum(r + i for r in range(size))
            np.testing.assert_array_equal(np.asarray(out), expected)

    elif scenario == "jax_fused":
        # Device-resident submissions: jax.Arrays fuse and reduce via the
        # on-chip pack→psum→unpack path on the XLA plane (zero host
        # transfers), or convert lazily on the host plane — values and
        # round-trip types must match on both.
        import jax.numpy as jnp

        tensors = [jnp.full((40,), float(rank + i), jnp.float32)
                   for i in range(8)]
        handles = [hvd.allreduce_async(t, average=False, name=f"mp.jaxf.{i}")
                   for i, t in enumerate(tensors)]
        for i, h in enumerate(handles):
            out = hvd.synchronize(h)
            assert isinstance(out, jax.Array), type(out)
            expected = sum(r + i for r in range(size))
            np.testing.assert_array_equal(np.asarray(out), expected)
        # averaging of a device result happens on device
        avg = hvd.allreduce(jnp.full((8,), float(rank + 1)), average=True,
                            name="mp.jax.avg")
        np.testing.assert_allclose(np.asarray(avg),
                                   sum(range(1, size + 1)) / size)
        # a mixed numpy+jax cycle falls back to one host-packed buffer;
        # both callers still get their framework type back
        hj = hvd.allreduce_async(jnp.arange(6, dtype=jnp.float32),
                                 average=False, name="mp.jax.mix.j")
        hn = hvd.allreduce_async(np.arange(6, dtype=np.float32),
                                 average=False, name="mp.jax.mix.n")
        outj, outn = hvd.synchronize(hj), hvd.synchronize(hn)
        assert isinstance(outj, jax.Array) and isinstance(outn, np.ndarray)
        np.testing.assert_array_equal(np.asarray(outj),
                                      np.arange(6, dtype=np.float32) * size)
        np.testing.assert_array_equal(outn,
                                      np.arange(6, dtype=np.float32) * size)
        # bf16 — the MXU-native wire — must survive the trip
        hb = hvd.allreduce(jnp.ones((16,), jnp.bfloat16), average=False,
                           name="mp.jax.bf16")
        np.testing.assert_array_equal(
            np.asarray(hb, dtype=np.float32), float(size))
        # device-resident ragged allgather
        g = hvd.allgather(jnp.full((rank + 1, 3), float(rank)),
                          name="mp.jax.gather")
        assert isinstance(g, jax.Array), type(g)
        np.testing.assert_array_equal(
            np.asarray(g),
            np.concatenate([np.full((r + 1, 3), float(r), np.float32)
                            for r in range(size)]))
        # device-resident broadcast: non-root Inf garbage must not leak,
        # narrow int dtypes must widen losslessly and cast back
        root = size - 1
        y = (jnp.full((5,), 7.0) if rank == root
             else jnp.full((5,), jnp.inf))
        b = hvd.broadcast(y, root_rank=root, name="mp.jax.bcast")
        assert isinstance(b, jax.Array), type(b)
        np.testing.assert_array_equal(np.asarray(b), 7.0)
        bi = hvd.broadcast(jnp.arange(4, dtype=jnp.int8) + rank,
                           root_rank=0, name="mp.jax.bcast.i8")
        assert np.asarray(bi).dtype == np.int8
        np.testing.assert_array_equal(np.asarray(bi),
                                      np.arange(4, dtype=np.int8))

    elif scenario == "allgather":
        # ragged first dims: rank r contributes r+1 rows of value r
        x = np.full((rank + 1, 3), float(rank), dtype=np.float32)
        out = np.asarray(hvd.allgather(x, name="mp.gather"))
        expected = np.concatenate(
            [np.full((r + 1, 3), float(r), np.float32) for r in range(size)])
        np.testing.assert_array_equal(out, expected)

    elif scenario == "broadcast":
        root = size - 1
        x = np.full((4,), float(rank * 10 + 5), dtype=np.float32)
        out = np.asarray(hvd.broadcast(x, root_rank=root, name="mp.bcast"))
        np.testing.assert_array_equal(out, float(root * 10 + 5))
        # non-root buffer contents are ignored — even Inf/NaN garbage
        # (uninitialized params about to be overwritten) must not leak into
        # the result on any data plane
        y = (np.full((3,), 7.0, np.float32) if rank == root
             else np.full((3,), np.inf, np.float32))
        out2 = np.asarray(hvd.broadcast(y, root_rank=root, name="mp.bcast2"))
        np.testing.assert_array_equal(out2, 7.0)

    elif scenario == "mismatch":
        # rank-dependent shapes must error on ALL ranks
        # (reference: test_torch.py:270-366)
        x = np.ones((rank + 2, 2), dtype=np.float32)
        try:
            hvd.allreduce(x, name="mp.mismatch")
        except hvd.HorovodInternalError as exc:
            assert "Mismatched allreduce tensor shapes" in str(exc)
        else:
            raise AssertionError("expected coordinator error on all ranks")

    elif scenario == "torch_grad":
        # Autograd rules for the collectives across real ranks (reference
        # ``test_torch.py:377-428``): backward of allreduce is allreduce,
        # allgather backward slices the summed gradient, broadcast sends
        # all gradient to the root.
        import torch

        import horovod_tpu.torch as hvd_torch

        x = torch.arange(4, dtype=torch.float32, requires_grad=True)
        w = torch.full((4,), float(rank + 1))
        y = hvd_torch.allreduce(x, average=False, name="g.ar")
        (y * w).sum().backward()
        # grad_output = w; backward allreduce sums w over ranks
        np.testing.assert_array_equal(
            x.grad.numpy(), np.full(4, float(sum(range(1, size + 1)))))

        g = torch.ones(rank + 1, 2, requires_grad=True)  # ragged rows
        out = hvd_torch.allgather(g, name="g.gather")
        (out * float(rank + 1)).sum().backward()
        # grad_output = (rank+1)*ones per rank; summed over ranks then this
        # rank keeps its own row block
        np.testing.assert_array_equal(
            g.grad.numpy(),
            np.full((rank + 1, 2), float(sum(range(1, size + 1)))))

        b = torch.ones(3, requires_grad=True)
        root = size - 1
        bout = hvd_torch.broadcast(b, root_rank=root, name="g.bcast")
        (bout * float(rank + 1)).sum().backward()
        expected = (float(sum(range(1, size + 1)))
                    if rank == root else 0.0)
        np.testing.assert_array_equal(b.grad.numpy(), np.full(3, expected))

    elif scenario == "torch_unused":
        # Rank-dependent unused parameters (reference
        # ``test_force_allreduce``): a rank whose backward never touched a
        # param must still join that param's allreduce with zeros —
        # skipping a collective the peers wait on would deadlock — and all
        # ranks must end the step with identical weights.
        import torch

        import horovod_tpu.torch as hvd_torch

        torch.manual_seed(5)
        l1, l2 = torch.nn.Linear(4, 4), torch.nn.Linear(4, 2)
        named = ([("l1." + k, v) for k, v in l1.named_parameters()] +
                 [("l2." + k, v) for k, v in l2.named_parameters()])
        opt = hvd_torch.DistributedOptimizer(
            torch.optim.SGD([p for _, p in named], lr=0.1),
            named_parameters=named)
        hvd_torch.broadcast_parameters(dict(named), root_rank=0)
        x = torch.full((3, 4), float(rank + 1))
        loss = (l2(l1(x)).sum() if rank == 0 else l1(x).sum())
        loss.backward()
        opt.step()  # must not hang; rank>0 joins l2's allreduce with zeros
        w = torch.cat([p.detach().reshape(-1) for _, p in named])
        gathered = hvd_torch.allgather(w.reshape(1, -1),
                                       name="unused.check")
        for r in range(1, size):
            np.testing.assert_allclose(gathered[r].numpy(),
                                       gathered[0].numpy(), rtol=1e-6)

    elif scenario == "torch":
        import torch

        import horovod_tpu.torch as hvd_torch

        torch.manual_seed(1234)  # same init on all ranks
        model = torch.nn.Linear(4, 2)
        opt = hvd_torch.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=1.0),
            named_parameters=model.named_parameters())
        hvd_torch.broadcast_parameters(model.state_dict(), root_rank=0)
        hvd_torch.broadcast_optimizer_state(opt, root_rank=0)
        before = {k: v.clone() for k, v in model.state_dict().items()}

        # rank-dependent input -> rank-dependent grads; step must apply the
        # world-averaged gradient on every rank
        x = torch.full((8, 4), float(rank + 1))
        loss = model(x).sum()
        loss.backward()
        opt.step()

        # replicate the expected mean gradient locally
        ref = torch.nn.Linear(4, 2)
        ref.load_state_dict(before)
        grads = []
        for r in range(size):
            ref.zero_grad()
            loss_r = ref(torch.full((8, 4), float(r + 1))).sum()
            loss_r.backward()
            grads.append([p.grad.clone() for p in ref.parameters()])
        mean_grads = [sum(gs) / size for gs in zip(*grads)]
        for p, g, b in zip(model.parameters(), mean_grads,
                           [before["weight"], before["bias"]]):
            np.testing.assert_allclose(
                p.detach().numpy(), (b - 1.0 * g).numpy(), rtol=1e-5)

        # torch eager ops incl. bf16 wire
        t = torch.full((4,), float(rank), dtype=torch.bfloat16)
        out = hvd_torch.allreduce(t, average=True, name="mp.torch.bf16")
        assert out.dtype == torch.bfloat16
        np.testing.assert_allclose(out.float().numpy(),
                                   sum(range(size)) / size, rtol=1e-2)

    elif scenario == "torch_state":
        # divergent optimizer state: root restored (momentum populated),
        # workers fresh (state empty) — must NOT deadlock, and workers must
        # adopt root's buffers
        import torch

        import horovod_tpu.torch as hvd_torch

        torch.manual_seed(7)
        model = torch.nn.Linear(3, 2)
        opt = torch.optim.SGD(model.parameters(), lr=0.5, momentum=0.9)
        if rank == 0:
            model(torch.ones(4, 3)).sum().backward()
            opt.step()  # populates momentum buffers on root only
        hvd_torch.broadcast_optimizer_state(opt, root_rank=0)
        state = opt.state_dict()["state"]
        assert len(state) > 0, "workers did not adopt root's state"
        for pstate in state.values():
            buf = pstate.get("momentum_buffer")
            assert buf is not None and float(buf.abs().sum()) > 0

    elif scenario == "tf":
        import tensorflow as tf

        import horovod_tpu.tensorflow as hvd_tf

        # eager ops: rank-dependent values
        t = tf.fill((4,), float(rank + 1))
        out = hvd_tf.allreduce(t, average=False, name="mp.tf.sum")
        np.testing.assert_array_equal(out.numpy(),
                                      float(sum(range(1, size + 1))))

        # DistributedGradientTape: rank-dependent grads must average
        v = tf.Variable([1.0, 2.0])
        with tf.GradientTape() as tape:
            loss = tf.reduce_sum(v * float(rank + 1))
        tape = hvd_tf.DistributedGradientTape(tape)
        grads = tape.gradient(loss, [v])
        mean_scale = sum(r + 1 for r in range(size)) / size
        np.testing.assert_allclose(grads[0].numpy(), mean_scale, rtol=1e-6)

        # broadcast_variables: workers adopt root's value
        var = tf.Variable([float(rank * 10)] * 3)
        hvd_tf.broadcast_variables([var], root_rank=0)
        np.testing.assert_array_equal(var.numpy(), 0.0)

        # sparse IndexedSlices -> 2x allgather path
        s = tf.IndexedSlices(values=tf.fill((1, 2), float(rank + 1)),
                             indices=tf.constant([rank]),
                             dense_shape=tf.constant([size, 2]))
        rs = hvd_tf.allreduce(s, average=False, name="mp.tf.sparse")
        assert rs.values.shape[0] == size

    elif scenario == "tf_grad":
        # TF collective backward rules across real ranks — the tf twin of
        # torch_grad (reference gradient registrations mpi_ops.py:94-183).
        import tensorflow as tf

        import horovod_tpu.tensorflow as hvd_tf

        x = tf.Variable(np.arange(4, dtype=np.float32))
        w = tf.constant(float(rank + 1))
        with tf.GradientTape() as tape:
            loss = tf.reduce_sum(
                hvd_tf.allreduce(x, average=False, name="g.ar") * w)
        total = float(sum(range(1, size + 1)))
        np.testing.assert_array_equal(tape.gradient(loss, x).numpy(),
                                      np.full(4, total))

        g = tf.Variable(np.ones((rank + 1, 2), np.float32))  # ragged rows
        with tf.GradientTape() as tape:
            loss = tf.reduce_sum(
                hvd_tf.allgather(g, name="g.gather") * float(rank + 1))
        np.testing.assert_array_equal(tape.gradient(loss, g).numpy(),
                                      np.full((rank + 1, 2), total))

        b = tf.Variable(np.ones(3, np.float32))
        root = size - 1
        with tf.GradientTape() as tape:
            loss = tf.reduce_sum(
                hvd_tf.broadcast(b, root_rank=root,
                                 name="g.bcast") * float(rank + 1))
        expected = total if rank == root else 0.0
        np.testing.assert_array_equal(tape.gradient(loss, b).numpy(),
                                      np.full(3, expected))

    elif scenario == "tf_keras":
        import keras
        import tensorflow as tf  # noqa: F401

        import horovod_tpu.tensorflow.keras as hvd_keras

        np.random.seed(100 + rank)  # rank-divergent init: broadcast must fix
        keras.utils.set_random_seed(100 + rank)
        X = np.random.randn(32, 4).astype(np.float32)
        Y = np.sum(X, axis=1, keepdims=True)
        model = keras.Sequential([keras.layers.Dense(1)])
        opt = hvd_keras.DistributedOptimizer(
            keras.optimizers.SGD(learning_rate=0.05))
        model.compile(optimizer=opt, loss="mse")
        cbs = [hvd_keras.callbacks.BroadcastGlobalVariablesCallback(0),
               hvd_keras.callbacks.MetricAverageCallback()]
        model.fit(X, Y, batch_size=16, epochs=2, callbacks=cbs, verbose=0)
        # after the broadcast callback + averaged gradients, weights must be
        # bitwise identical on all ranks
        w = np.concatenate([np.ravel(v.numpy()) for v in model.weights])
        gathered = np.asarray(hvd_keras.allgather(
            w.reshape(1, -1), name="mp.keras.weights"))
        for r in range(size):
            np.testing.assert_array_equal(gathered[r], gathered[0])

    elif scenario == "cache_steady":
        # Steady-state negotiation bypass (docs/response-cache.md): the
        # same tensor set every step must turn into cache-bit cycles after
        # the first negotiated step, with BIT-EXACT results either way.
        # The parent runs this scenario with the cache on and with
        # HOROVOD_CACHE_CAPACITY=0 and compares the CACHE-HASH lines.
        import hashlib

        from horovod_tpu.ops.engine import get_engine

        digest = hashlib.sha256()
        steps, n_tensors = 12, 5
        for step in range(steps):
            handles = [hvd.allreduce_async(
                np.full((64,), float(rank * 17 + i) + 0.37 * i, np.float32),
                average=False, name=f"cs.{i}") for i in range(n_tensors)]
            for i, h in enumerate(handles):
                out = np.asarray(hvd.synchronize(h))
                # float32 accumulation in rank order — exactly the
                # coordinator's host combine, so equality is bitwise
                acc = np.zeros((64,), np.float32)
                for r in range(size):
                    acc = acc + np.full(
                        (64,), float(r * 17 + i) + 0.37 * i, np.float32)
                np.testing.assert_array_equal(out, acc)
                digest.update(out.tobytes())
        stats = get_engine().cache_stats()
        if int(os.environ.get("HOROVOD_CACHE_CAPACITY", "1") or 0) > 0:
            # idle ticks also ride the bitvector, so hit_cycles alone is
            # weak; miss_cycles < steps is the real claim — at least one
            # whole STEP negotiated through the bypass
            assert stats["miss_cycles"] >= 1, stats
            assert stats["miss_cycles"] < steps, stats
            assert stats["hit_cycles"] > 0, stats
            assert stats["entries"] >= 1, stats
        else:
            assert stats["capacity"] == 0, stats
            assert stats["hit_cycles"] == 0 == stats["miss_cycles"], stats
        print(f"CACHE-HASH {digest.hexdigest()}", flush=True)

    elif scenario == "cache_stall":
        # Acceptance: a stall injected DURING an all-hit steady state must
        # still escalate to RanksAbortedError within
        # HOROVOD_STALL_SHUTDOWN_TIME_S — the bypass keeps the
        # coordinator's stall check and escalation deadline running (a
        # cache hit must never mask a dead rank). Parent env: warning 1s,
        # shutdown 2s, cache on, Python controller.
        import time

        from horovod_tpu.ops.engine import get_engine

        engine = get_engine()
        for _ in range(3):  # build the warm steady state
            hvd.allreduce(np.ones((16,), np.float32), average=False,
                          name="cst.steady")
        trap = None
        if rank == 0:
            # planted stall: rank 1 never submits this name
            trap = hvd.allreduce_async(np.ones((4,), np.float32),
                                       average=False, name="cst.trap")
        t0 = time.monotonic()
        aborted = False
        try:
            while time.monotonic() - t0 < 20.0:
                hvd.allreduce(np.full((16,), 2.0, np.float32),
                              average=False, name="cst.steady")
                time.sleep(0.005)
        except (hvd.RanksAbortedError, RuntimeError) as exc:
            assert "shut down" in str(exc), exc
            aborted = True
        assert aborted, "stall never escalated during the warm steady state"
        assert time.monotonic() - t0 < 15.0
        stats = engine.cache_stats()
        assert stats["hit_cycles"] > 0, (
            "steady state never reached the bypass; this scenario would "
            "not be testing stall-under-hit at all", stats)
        if rank == 0:
            try:
                hvd.synchronize(trap)
            except hvd.RanksAbortedError as exc:
                assert exc.ranks == [1], exc.ranks
            else:
                raise AssertionError("trap handle did not carry the abort")

    elif scenario == "stall_abort":
        # Abort-instead-of-hang (HOROVOD_STALL_SHUTDOWN_TIME_S): rank 0
        # submits a tensor the other rank NEVER submits. The reference
        # behavior is an infinite hang behind a stall warning; with the
        # shutdown deadline set (parent env: warning 1s, shutdown 2s) the
        # coordinator escalates into a structured world abort and rank 0
        # raises RanksAbortedError naming the missing rank — well before
        # the parent's harness timeout.
        import time

        from horovod_tpu.ops.engine import get_engine

        engine = get_engine()
        if rank == 0:
            t0 = time.monotonic()
            try:
                hvd.allreduce(np.ones((4,), np.float32), average=False,
                              name="sa.trap")
            except hvd.RanksAbortedError as exc:
                assert exc.ranks == [1], exc.ranks
                assert "shut down" in str(exc), exc
            else:
                raise AssertionError(
                    "expected RanksAbortedError from the stall deadline")
            assert time.monotonic() - t0 < 20.0
        else:
            # the permanently-absent rank: keep cycling (the engine loop
            # does) but never submit sa.trap; the escalated shutdown must
            # stop this engine too instead of leaving it parked
            assert engine._stopped.wait(25.0), \
                "absent rank's engine not stopped by the escalation"

    elif scenario == "object_edge":
        # broadcast_object edge cases: None payload, empty bytes, a blob
        # far above the (parent-shrunk) fusion threshold, and an exact
        # pickle round-trip on non-root ranks.
        import pickle

        out = hvd.broadcast_object(None if rank == 0 else "junk",
                                   root_rank=0, name="oe.none")
        assert out is None, out
        out = hvd.broadcast_object(b"" if rank == 0 else None,
                                   root_rank=0, name="oe.empty")
        assert out == b"", out
        out = hvd.broadcast_object([] if rank == 0 else None,
                                   root_rank=0, name="oe.emptylist")
        assert out == [], out
        blob = bytes(range(256)) * 4096  # 1 MiB >> threshold
        out = hvd.broadcast_object({"blob": blob} if rank == 0 else None,
                                   root_rank=0, name="oe.big")
        assert out["blob"] == blob
        obj = {"a": [1, 2, {"b": (3.5, "s")}], "t": ("x", None),
               "arr": np.arange(7, dtype=np.int16)}
        out = hvd.broadcast_object(obj if rank == 0 else None,
                                   root_rank=0, name="oe.exact")
        # non-root ranks must see a payload that round-trips pickle
        # exactly (same bytes as root's serialization)
        ref = {**obj, "arr": obj["arr"]}
        assert pickle.dumps(out) == pickle.dumps(ref)
        np.testing.assert_array_equal(out["arr"], obj["arr"])

    elif scenario == "stall":
        # rank 0 submits immediately; rank 1 delays past the stall window so
        # the coordinator must print the stall warning naming the missing
        # rank (CheckForStalledTensors, operations.cc:1625-1672) — then the
        # late submission still completes correctly.
        import time

        x = np.ones((4,), dtype=np.float32)
        if rank == 1:
            time.sleep(3.0)
        out = hvd.allreduce(x, average=False, name="stalled_tensor")
        np.testing.assert_array_equal(np.asarray(out), float(size))

    elif scenario == "autotune":
        # end-to-end autotune on a multi-process world: sustained eager
        # traffic must drive the coordinator's tuner (knob movement is
        # asserted by the parent via HOROVOD_AUTOTUNE_LOG) while results
        # stay correct and the tuned cycle time propagates to workers
        for batch in range(40):
            tensors = [np.full((500,), float(rank + i), np.float32)
                       for i in range(6)]
            handles = [hvd.allreduce_async(t, average=False,
                                           name=f"at.{batch}.{i}")
                       for i, t in enumerate(tensors)]
            for i, h in enumerate(handles):
                out = np.asarray(hvd.synchronize(h))
                np.testing.assert_array_equal(
                    out, float(sum(r + i for r in range(size))))

    elif scenario == "peer_death":
        # Failure detection under load (reference semantics: an exception or
        # exit on one rank shuts the whole world down,
        # ``operations.cc:1942-1957``): the last rank dies abruptly with
        # tensors in flight; every survivor must unblock with
        # SHUT_DOWN_ERROR well inside the stall window instead of hanging.
        import time

        victim = size - 1
        # Barrier: the kill must hit a fully-formed world mid-stream, not a
        # rank still inside init (that is a different failure, surfaced as
        # an init error).
        hvd.allreduce(np.ones((4,), np.float32), average=False,
                      name="pd.barrier")
        if rank == victim:
            # Same shapes as the survivors: under heavy CPU load the
            # victim's cycle can ship these before the _exit lands, and a
            # shape mismatch would then surface as a coordinator ERROR
            # instead of the death-abort this scenario pins.
            for i in range(3):
                hvd.allreduce_async(np.ones((256,), np.float32),
                                    average=False, name=f"pd.{i}")
            os._exit(3)  # no shutdown message, no atexit — a real crash
        handles = [hvd.allreduce_async(np.full((256,), float(rank),
                                               np.float32),
                                       average=False, name=f"pd.{i}")
                   for i in range(8)]
        t0 = time.monotonic()
        try:
            for h in handles:
                hvd.synchronize(h)
        except hvd.HorovodInternalError as exc:
            assert "shut down" in str(exc), exc
        else:
            raise AssertionError("expected SHUT_DOWN_ERROR after peer death")
        elapsed = time.monotonic() - t0
        assert elapsed < 30.0, f"unblocked only after {elapsed:.1f}s"

    elif scenario == "peer_death_xla":
        # The realistic TPU failure mode: a rank dies while its peers are
        # blocked INSIDE a compiled XLA collective (gloo/ICI — not a TCP
        # recv the controller can poison). The controller attributes the
        # death and pushes the abort over the watch channel; survivors'
        # engines abandon the stuck collective (``_DevicePlaneWorker``)
        # and every outstanding handle fails with SHUT_DOWN_ERROR.
        import time

        import jax.numpy as jnp

        from horovod_tpu.ops.engine import get_engine

        victim = size - 1
        hvd.allreduce(np.ones((4,), np.float32), average=False,
                      name="px.barrier")
        engine = get_engine()
        assert engine._plane is not None, "scenario requires the XLA plane"
        if rank == victim:
            # Deterministic timing: this rank negotiates the collective
            # (so every peer will issue the compiled psum) but dies at
            # execution time, exactly when the survivors are inside it.
            engine._plane.allreduce_onchip = \
                lambda *a, **k: os._exit(3)  # type: ignore[method-assign]
            hvd.allreduce_async(jnp.ones((64,), jnp.float32),
                                average=False, name="px.trap")
            time.sleep(60.0)  # the engine executes + exits from its loop
            raise AssertionError("victim failed to die")
        h = hvd.allreduce_async(jnp.full((64,), float(rank), jnp.float32),
                                average=False, name="px.trap")
        t0 = time.monotonic()
        try:
            hvd.synchronize(h)
        except hvd.HorovodInternalError as exc:
            assert "shut down" in str(exc), exc
        else:
            raise AssertionError(
                "expected SHUT_DOWN_ERROR after peer death inside a "
                "compiled collective")
        elapsed = time.monotonic() - t0
        assert elapsed < 30.0, f"unblocked only after {elapsed:.1f}s"
        # Survivors exit hard: the jax.distributed shutdown barrier can
        # never complete with the victim gone (the coordination service
        # would FATAL this process ~90s later at interpreter teardown) —
        # like the reference's survivors after mpirun kills a world.
        print(f"WORKER-OK {os.environ['HOROVOD_RANK']}", flush=True)
        os._exit(0)

    elif scenario == "local_crash":
        # A rank whose ENGINE dies from a local fault while its process
        # stays alive must still be treated as a rank death: its crash-path
        # close carries no clean-detach, so the controller aborts the peers
        # instead of leaving them parked in the cycle rendezvous forever.
        import time

        from horovod_tpu.ops.engine import get_engine

        victim = size - 1
        hvd.allreduce(np.ones((4,), np.float32), average=False,
                      name="lc.barrier")
        if rank == victim:
            engine = get_engine()

            def _boom(entry):
                raise RuntimeError("injected local engine fault")

            engine._request_of = _boom
            h = hvd.allreduce_async(np.ones((8,), np.float32),
                                    name="lc.trigger")
            try:
                hvd.synchronize(h)
            except hvd.HorovodInternalError:
                pass  # own handle flushed by the dying loop
            time.sleep(5.0)  # stay alive: only the engine is dead
            return  # skip the hvd.shutdown() handshake below via early exit
        handles = [hvd.allreduce_async(np.full((64,), float(rank),
                                               np.float32),
                                       average=False, name=f"lc.{i}")
                   for i in range(4)]
        t0 = time.monotonic()
        try:
            for h in handles:
                hvd.synchronize(h)
        except hvd.HorovodInternalError as exc:
            assert "shut down" in str(exc), exc
        else:
            raise AssertionError("expected SHUT_DOWN_ERROR after engine "
                                 "death on a peer")
        assert time.monotonic() - t0 < 30.0

    elif scenario == "object":
        obj = {"root": "payload", "rank": 0} if rank == 0 else None
        out = hvd.broadcast_object(obj, root_rank=0)
        assert out == {"root": "payload", "rank": 0}

    else:
        raise ValueError(f"unknown scenario {scenario}")

    hvd.shutdown()


def _subset_scenario(scenario: str) -> None:
    """Subset worlds (``hvd.init(ranks=[...])``): members form a communicator
    in list order; non-members get a self-world; launcher world-rank 0
    hosts the controller service even as a non-member
    (reference ``operations.cc:1728-1742`` / ``common/__init__.py:58-84``).

    subset_02: 3-process world, ranks=[0, 2]  (member coordinator host)
    subset_12: 3-process world, ranks=[1, 2]  (NON-member coordinator host)
    """
    world_rank = int(os.environ["HOROVOD_RANK"])
    subset = {"subset_02": [0, 2], "subset_12": [1, 2]}[scenario]
    hvd.init(ranks=subset)
    if world_rank in subset:
        my = subset.index(world_rank)
        assert hvd.rank() == my, (hvd.rank(), my)
        assert hvd.size() == len(subset)
        # members allreduce their WORLD rank: the sum proves exactly the
        # subset participated
        out = hvd.allreduce(np.full((4,), float(world_rank), np.float32),
                            average=False, name="sub.sum")
        np.testing.assert_array_equal(np.asarray(out), float(sum(subset)))
        # broadcast from the last subset member
        root = len(subset) - 1
        b = hvd.broadcast(np.full((2,), float(world_rank), np.float32),
                          root_rank=root, name="sub.bcast")
        np.testing.assert_array_equal(np.asarray(b), float(subset[-1]))
    else:
        # non-member: self-world; collectives act locally and cannot hang
        assert hvd.rank() == 0 and hvd.size() == 1
        out = hvd.allreduce(np.full((4,), 7.0, np.float32),
                            average=False, name="sub.self")
        np.testing.assert_array_equal(np.asarray(out), 7.0)
        if world_rank == 0:
            # service host: stay alive while the members finish (shutdown's
            # grace period would cover this, but do not rely on timing)
            import time

            time.sleep(3.0)
    hvd.shutdown()


if __name__ == "__main__":
    main()
    print(f"WORKER-OK {os.environ['HOROVOD_RANK']}", flush=True)
    if _coord:
        # _exit skips atexit, so leave the multi-process JAX world
        # gracefully first — an abrupt drop of the rank-0 coordination
        # service errors peers still inside their own teardown barrier.
        jax.distributed.shutdown()
    # Skip interpreter teardown: with torch AND jax loaded in one process,
    # C++ static-destructor ordering at exit can abort (SIGABRT) under
    # heavy scheduling pressure — observed once on the loaded single-core
    # CI box (torch_grad rank died -6 AFTER all assertions and
    # hvd.shutdown() completed). Everything the scenarios verify has
    # already run; _exit only skips the hazardous library unwind.
    os._exit(0)
