"""Streaming deployment responses (reference: serve streaming handles —
DeploymentResponseGenerator): generator methods stream chunks over the
core streaming-generator protocol (ObjectRefGenerator items with
backpressure); errors mid-stream surface to the consumer with their
original type. Cancellation: a client that drops/closes the iterator
mid-generation must run the replica-side generator's finally path NOW
(freeing inference-engine slots etc.), and a replica killed mid-stream
must come back with a clean slot pool."""

import time

import pytest

import ray_tpu
from ray_tpu import serve


def test_streaming_handle(ray_start):
    @serve.deployment
    class Streamer:
        def __call__(self, n):
            for i in range(n):
                yield {"token": i}

        def fail_midway(self, n):
            for i in range(n):
                if i == 3:
                    raise ValueError("midstream boom")
                yield i

    serve.run(Streamer.bind(), name="stream-app")
    h = serve.get_app_handle("stream-app").options(stream=True)
    chunks = list(h.remote(5))
    assert chunks == [{"token": i} for i in range(5)]

    gen = h.fail_midway.remote(10)
    got = []
    with pytest.raises(ValueError, match="midstream boom"):
        for c in gen:
            got.append(c)
    assert got == [0, 1, 2]


# --------------------------------------------------------------------------
# cancellation: replica-side finally must run when the client walks away
# --------------------------------------------------------------------------

class _Tracker:
    """Counts generator entry/exit so tests can see whether the
    replica-side finally ran."""

    def __init__(self):
        self.active = 0
        self.closed = 0

    def stream(self, n):
        self.active += 1
        try:
            for i in range(n):
                yield i
        finally:
            self.active -= 1
            self.closed += 1

    def state(self):
        return (self.active, self.closed)


def _tiny_llm_config():
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig
    return TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=512, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False)


def test_replica_stream_close_runs_user_finally():
    """No cluster needed: closing the replica's handle_stream generator
    mid-iteration must close the USER generator (GeneratorExit through
    its finally) and release the ongoing count."""
    import cloudpickle

    from ray_tpu.serve.replica import Replica
    r = Replica(cloudpickle.dumps(_Tracker), (), {}, False)
    g = r.handle_stream("stream", (1000,), {})
    assert next(g) == 0
    assert next(g) == 1
    assert r.handle_request("state", (), {}) == (1, 0)
    g.close()
    assert r.handle_request("state", (), {}) == (0, 1)
    assert r.get_queue_len() == 0


def test_llm_deployment_generator_exit_frees_slot():
    """No cluster needed: dropping LLMDeployment's streaming generator
    mid-generation cancels the engine request — the slot returns to the
    pool and the queue drains (the contract the Serve path relies on)."""
    from ray_tpu.inference import LLMDeployment
    dep = LLMDeployment(_tiny_llm_config(), n_slots=2, max_len=256,
                        prefill_chunk=8, prefill_budget=16)
    try:
        gen = dep([1, 2, 3, 4], max_new_tokens=200)
        got = [next(gen) for _ in range(3)]
        assert len(got) == 3
        assert dep.stats()["slots_occupied"] == 1
        gen.close()                      # GeneratorExit -> cancel
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            st = dep.stats()
            if st["slots_free"] == 2 and st["queue_depth"] == 0:
                break
            time.sleep(0.02)
        st = dep.stats()
        assert st["slots_free"] == 2 and st["queue_depth"] == 0, st
        # the slot is immediately reusable
        assert len(dep.generate([5, 6], max_new_tokens=4)) == 4
        assert dep.stats()["decode_compile_count"] == 1
    finally:
        dep.engine.stop()


def test_stream_cancellation_frees_slot_over_serve(ray_start):
    """Client drops a Serve streaming iterator mid-generation: the
    engine slot frees and the queue metrics decrement."""
    from ray_tpu.inference import LLMDeployment
    dep = serve.deployment(LLMDeployment)
    serve.run(dep.bind(_tiny_llm_config(), n_slots=2, max_len=512,
                       prefill_chunk=8, prefill_budget=16),
              name="llm-cancel")
    h = serve.get_app_handle("llm-cancel")
    stream = h.options(stream=True)
    gen = stream.remote([1, 2, 3, 4], max_new_tokens=400)
    got = []
    for tok in gen:
        got.append(tok)
        if len(got) >= 3:
            break
    gen.close()                          # client walks away mid-stream
    deadline = time.monotonic() + 30
    st = {}
    while time.monotonic() < deadline:
        st = h.stats.remote().result()
        if st["slots_free"] == st["n_slots"] and st["queue_depth"] == 0:
            break
        time.sleep(0.2)
    assert st.get("slots_free") == st.get("n_slots"), st
    assert st.get("queue_depth") == 0, st
    # engine still healthy: a fresh request completes
    out = list(stream.remote([9, 8, 7], max_new_tokens=5))
    assert len(out) == 5
    serve.delete("llm-cancel")


def test_kill_replica_mid_stream_reclaims_slots(ray_start):
    """Chaos: a replica killed mid-stream is replaced by the controller
    and the replacement's slot pool is fully free (no leaked slots from
    the severed stream); serving resumes."""
    from ray_tpu.inference import LLMDeployment
    from ray_tpu.util.chaos import ServeReplicaKiller
    dep = serve.deployment(LLMDeployment)
    serve.run(dep.bind(_tiny_llm_config(), n_slots=2, max_len=512,
                       prefill_chunk=8, prefill_budget=16),
              name="llm-chaos")
    h = serve.get_app_handle("llm-chaos")
    gen = h.options(stream=True).remote([1, 2, 3, 4], max_new_tokens=400)
    got = [next(gen) for _ in range(2)]
    assert len(got) == 2
    killer = ServeReplicaKiller("llm-chaos", "LLMDeployment")
    assert killer.kill_one()
    # the severed stream surfaces an error (type depends on where the
    # death lands: mid-item vs between items)
    with pytest.raises(Exception):
        for _ in range(1000):
            next(gen)
    assert killer.wait_for_replacement(timeout_s=90)
    deadline = time.monotonic() + 60
    st = {}
    while time.monotonic() < deadline:
        try:
            st = h.stats.remote().result()
            if st.get("slots_free") == st.get("n_slots"):
                break
        except Exception:
            pass
        time.sleep(0.5)
    assert st.get("slots_free") == st.get("n_slots"), st
    out = list(h.options(stream=True).remote([5, 6], max_new_tokens=4))
    assert len(out) == 4
    serve.delete("llm-chaos")
