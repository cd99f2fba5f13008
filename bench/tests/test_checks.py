"""What decides ``correct``: the reference against the program, the
checksums, and a run with the timed path broken, which has to come out not
correct. Also the control, which has to fail its limit."""

import numpy as np
import pytest

from conftest import TINY, add_cell
from harness import checksum, model, reference, runner
import jax
import jax.numpy as jnp

SEED = 2 ** 31 + 11


def test_reference_matches_program_in_float32():
    from repro.serving.engine import jit_decode_step, jit_prefill

    conf = dict(TINY, torch_dtype="float32", tie_word_embeddings=True)
    s, cfg = model.Shapes.of(conf), model.program_config(conf)
    w = model.make_weights(conf, 7)
    seq = np.random.default_rng(0).integers(0, 512, 40).astype(np.int32)
    logits, caches, n = jit_prefill(w, jnp.asarray(seq[None, :30]), cfg,
                                    max_len=64)
    prog = [np.asarray(logits[0])]
    for i in range(30, 39):
        logits, caches = jit_decode_step(w, jnp.asarray(seq[i:i + 1]), caches,
                                         n, cfg)
        n = n + 1
        prog.append(np.asarray(logits[0]))
    with jax.default_matmul_precision("highest"):
        ref = reference.logits_at(model.flat_layout(w), s, [seq],
                                  [np.arange(29, 39)])[0]
    np.testing.assert_allclose(np.stack(prog), ref, atol=1e-4)


def test_checksums_agree():
    doc = np.random.default_rng(1).integers(
        0, 1 << 16, (64, 2, 3), dtype=np.uint16)
    doc = (doc & np.uint16(0x807F)) | np.uint16(0x3B80)
    doc = doc.view(jnp.bfloat16)
    host = checksum.host_checksums(doc, 16, 4)
    for b in range(4):
        part = jnp.asarray(doc[:16 * (b + 1)])
        assert int(checksum.checksum(part)) == host[b]
        assert int(checksum.checksum(part.reshape(-1))) == host[b]
    moved = doc.copy()
    moved[[3, 5]] = moved[[5, 3]]
    assert int(checksum.checksum(jnp.asarray(moved[:16]))) != host[0]


# A fetch cell wide enough per token (32 KiB) that prefixes pass the
# engine's 12 MB fallback and are split over the relay chips.
WIDE = dict(TINY, name="tinykv", hidden_size=256, num_attention_heads=32,
            num_key_value_heads=32, head_dim=128, intermediate_size=512)
WIDE_FETCH = (
    {"driver": "fetch", "items": 8,
     "prefix": {"groups": 2, "document_tokens": 2048, "block_tokens": 256,
                "tokens": {"grid": [1024, 2048, 512]}}},
    {"trace_seconds": 1, "limits": {"fetches_wrong": 0}},
    "qwen-7b-chat-l16.fetch.relay4", 4)


@pytest.fixture(scope="module")
def root(tiny_root):
    if not (tiny_root / "bench" / "cells" / "tinykv.fetch.relay4.json").exists():
        add_cell(tiny_root, "tinykv.fetch.relay4", *WIDE_FETCH, config=WIDE)
    return tiny_root


def _run(root, cell, control=False):
    return runner.execute(root, cell, SEED, 1.0, False, require_chip=False,
                          control=control)[0]


def test_wide_fetch_relays(root):
    result = _run(root, "tinykv.fetch.relay4")
    assert result["correct"]


@pytest.mark.parametrize("cell", ["tiny.switch", "tiny.docqa"])
def test_token_altered_where_produced(root, cell, monkeypatch):
    from repro.serving.engine import FunctionalServer

    decode = FunctionalServer._decode_one

    def altered(self, req):
        decode(self, req)
        req.generated[-1] = (req.generated[-1] + 1) % self.cfg.vocab

    monkeypatch.setattr(FunctionalServer, "_decode_one", altered)
    assert not _run(root, cell)["correct"]


def test_wake_chunk_altered(root, monkeypatch):
    """One bit of the first chunk of every leaf woken is flipped where it
    lands: the served tokens barely move, the weights' checksums catch it."""
    from repro.core import jax_backend
    from repro.core.transfer_task import Direction

    launch = jax_backend.JaxBackend.launch

    def altered(self, mt, route, on_done):
        launch(self, mt, route, on_done)
        if mt.direction == Direction.H2D and mt.seq == 0:
            chunks = mt.parent.dst.chunks
            bits = jax.lax.bitcast_convert_type(chunks[0], jnp.uint16)
            chunks[0] = jax.lax.bitcast_convert_type(
                bits.at[0].set(bits[0] ^ 1), chunks[0].dtype)

    monkeypatch.setattr(jax_backend.JaxBackend, "launch", altered)
    result = _run(root, "tiny.switch")
    assert not result["correct"]
    checks = result["checks"]
    assert checks["weights_wrong"]["value"] > 0
    assert checks["served_logit_gap"]["value"] \
        <= checks["served_logit_gap"]["limit"]


def test_relay_hop_left_out(root, monkeypatch):
    from repro.core import jax_backend

    put = jax.device_put

    def launch(self, mt, route, on_done):
        if route.is_direct:
            return original(self, mt, route, on_done)
        # the chunk is staged on the relay chip but never crosses to the
        # target: what lands there is a buffer of zeros
        task = mt.parent
        n = mt.nbytes // task.src.itemsize
        task.dst.add(mt.seq, put(np.zeros(n, task.src.dtype),
                                 self.devices[route.dest]))
        self._done.append(on_done)

    original = jax_backend.JaxBackend.launch
    monkeypatch.setattr(jax_backend.JaxBackend, "launch", launch)
    assert not _run(root, "tinykv.fetch.relay4")["correct"]


def test_fetched_answer_altered(root, monkeypatch):
    from repro.core import jax_backend

    result = jax_backend.ChunkAssembler.result

    def altered(self, shape, dtype):
        out = result(self, shape, dtype)
        flat = out.reshape(-1)
        return flat.at[flat.shape[0] // 2].add(1).reshape(shape)

    monkeypatch.setattr(jax_backend.ChunkAssembler, "result", altered)
    assert not _run(root, "tinykv.fetch.relay4")["correct"]


@pytest.mark.parametrize("cell,name", [
    ("tiny.switch", "served_logit_gap"), ("tiny.docqa", "served_logit_gap"),
    ("tinykv.fetch.relay4", "fetches_wrong")])
def test_control_fails(root, cell, name):
    """The reference in float8 in the program's place reads above the
    limit the program's runs stay under, and is judged not correct."""
    result = _run(root, cell, control=True)
    assert result["correct"]
    assert result["control"][name] > result["checks"][name]["limit"]
    assert result["control_correct"] is False
