"""The round's spans, device scopes and epoch counters (core.telemetry).

* every round records one ``round`` root with its stages nested under it,
  in the order the round runs them, on each engine body;
* the epoch counters: each participant's optimizer steps are its real
  batch count times the epochs, against the padded count every
  participant runs;
* the compiled round programs name their stages in the ``op_name``
  metadata, and every slot search of the wire sits under ``compact`` or
  ``unpack``;
* the round logs and the checkpoint do not depend on what telemetry holds.
"""
import math
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.feds3a_cnn import CNNConfig
from repro.core import FedS3AConfig, FedS3ATrainer
from repro.core import telemetry as tm
from repro.data import make_fleet_dataset

TEST_CNN = CNNConfig(name="feds3a-cnn-telemetry", conv_filters=(8, 8),
                     hidden=16)
FLAT = [tm.PROLOGUE, tm.KEYS, tm.GATHER, tm.CLIENT_EPOCH, tm.UPLOAD,
        tm.SERVER_EPOCH, tm.GROUPING, tm.WEIGHTS, tm.FINALIZE, tm.STORE,
        tm.EPILOGUE]
# the sharded engine trains inside its upload stage and groups on device
SHARDED = [tm.PROLOGUE, tm.KEYS, tm.GATHER, tm.UPLOAD, tm.SERVER_EPOCH,
           tm.WEIGHTS, tm.FINALIZE, tm.STORE, tm.EPILOGUE]


@pytest.fixture(scope="module")
def data():
    return make_fleet_dataset(8, scale=0.003, seed=0)


def _trainer(data, **kw):
    return FedS3ATrainer(data, FedS3AConfig(cnn=TEST_CNN, C=0.5, **kw))


@pytest.mark.parametrize("kw,order", [
    (dict(engine="batched"), FLAT),
    (dict(engine="batched", wire_format="csr_q", error_feedback=True), FLAT),
    (dict(engine="batched", chunk_size=2048), FLAT),
    (dict(engine="sharded"), SHARDED),
], ids=["batched", "batched-csr_q-ef", "chunked", "sharded"])
def test_each_round_records_one_root_with_its_stages_in_order(data, kw,
                                                              order):
    tr = _trainer(data, **kw)
    tm.clear()
    for _ in range(2):
        tr.run_round()
    records = tm.rounds()
    assert len(records) == 2
    assert records[0]["round"] != records[1]["round"]
    for rec in records:
        spans = {s["id"]: s for s in rec["spans"]}
        roots = [s for s in spans.values() if s["parent"] is None]
        assert [s["name"] for s in roots] == [tm.ROUND]
        root = roots[0]
        assert root["id"] == rec["round"]
        assert all(s["round"] == rec["round"] for s in spans.values())
        children = sorted((s for s in spans.values()
                           if s["parent"] == root["id"]),
                          key=lambda s: s["start_ns"])
        assert [s["name"] for s in children] == order
        for s in spans.values():
            if s["parent"] is not None:
                p = spans[s["parent"]]
                assert p["start_ns"] <= s["start_ns"] <= s["end_ns"] \
                    <= p["end_ns"]
        by_name = {s["name"]: s for s in spans.values()}
        assert by_name[tm.SCHEDULER]["parent"] == by_name[tm.PROLOGUE]["id"]
        waits = [s for s in spans.values() if s["wait"]]
        if tm.GROUPING in by_name:
            assert [s["name"] for s in waits] == [tm.HIST_FETCH]
            assert waits[0]["parent"] == by_name[tm.GROUPING]["id"]
        else:
            assert waits == []


def test_ring_keeps_the_newest_rounds():
    rec = tm.Recorder(maxlen=3)
    for _ in range(5):
        with rec.span(tm.ROUND):
            with rec.span(tm.PROLOGUE):
                rec.count("n", 1)
    assert len(rec.rounds()) == 3
    assert [r["round"] for r in rec.rounds(2)] == \
        [r["round"] for r in rec.rounds()[1:]]
    assert len(rec.rounds(10)) == 3 and rec.rounds(0) == []
    assert rec.rounds(1)[0]["counters"] == {"n": 1}
    # a span outside any round is traced but not recorded
    with rec.span(tm.UPLOAD):
        rec.count("n", 2)
    assert len(rec.rounds()) == 3


@pytest.mark.parametrize("epochs", [1, 2])
@pytest.mark.parametrize("chunk_size", [0, 2048], ids=["flat", "chunked"])
def test_epoch_counters_count_real_and_padded_steps(data, epochs,
                                                    chunk_size):
    tr = _trainer(data, engine="batched", epochs=epochs,
                  chunk_size=chunk_size)
    B = tr.cfg.batch_size
    nb = max(math.ceil(len(c["x"]) / B) for c in data["clients"])
    tm.clear()
    tr.run_round()
    counters = tm.rounds(1)[0]["counters"]
    sizes = [len(data["clients"][i]["x"]) for i in tr.logs[-1].participants]
    live = counters[tm.CLIENT_LIVE_STEPS]
    assert isinstance(live, jax.Array)
    np.testing.assert_array_equal(
        np.asarray(live), [math.ceil(n / B) * epochs for n in sizes])
    assert int(np.sum(live)) == sum(math.ceil(n / B) for n in sizes) * epochs
    assert counters[tm.CLIENT_STEPS_RUN] == nb * epochs
    assert isinstance(counters[tm.CLIENT_STEPS_RUN], int)
    loss = np.asarray(counters[tm.CLIENT_LOSS])
    assert loss.shape == (len(sizes),) and np.all(np.isfinite(loss))


def _scope_paths(text):
    """The telemetry scope names along each op_name of compiled HLO text
    (the last part of an op_name is the primitive)."""
    return {"/".join(c for c in op.split("/")[:-1] if c in tm.SCOPES)
            for op in re.findall(r'op_name="([^"]*)"', text)}


def _searches_outside_a_search_scope(text):
    return [op for op in re.findall(r'op_name="([^"]*)"', text)
            if "searchsorted" in op
            and not {tm.COMPACT, tm.UNPACK} & set(op.split("/")[:-1])]


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["ref", "pallas"])
def test_compiled_round_programs_name_their_stages(data, use_kernels):
    tr = _trainer(data, engine="batched", wire_format="csr_q",
                  error_feedback=True, use_kernels=use_kernels)
    texts = {}

    def compiled_text(name, factory):
        def make(*a):
            fn = factory(*a)

            def call(*args):
                texts[name] = fn.lower(*args).compile().as_text()
                return fn(*args)
            return call
        return make

    tr._upload_fn = compiled_text("upload", tr._upload_fn)
    tr._finalize_fn = compiled_text("finalize", tr._finalize_fn)
    epoch = tr.batched_epoch

    def batched_epoch(*args):
        texts["epoch"] = jax.jit(epoch).lower(*args).compile().as_text()
        return epoch(*args)

    tr.batched_epoch = batched_epoch
    tr.run_round()
    upload, finalize = _scope_paths(texts["upload"]), \
        _scope_paths(texts["finalize"])
    assert {"upload/threshold", "upload/compact", "upload/mask",
            "upload/quantize", "upload/quantize/compact",
            "upload/residual/threshold", "upload/residual/compact",
            "upload/histogram"} <= upload
    assert {"finalize/blend", "finalize/blend/unpack",
            "finalize/distribute/compact"} <= finalize
    assert tm.CLIENT_EPOCH in _scope_paths(texts["epoch"])
    for name in ("upload", "finalize"):
        assert "searchsorted" in texts[name]
        assert _searches_outside_a_search_scope(texts[name]) == []


def _checkpoint_sections(tr):
    path = Path(tr.save_checkpoint())
    return {f.name: f.read_bytes() for f in sorted(path.glob("*.msgpack"))}


def test_round_logs_and_checkpoint_do_not_depend_on_telemetry(data,
                                                              tmp_path):
    runs = []
    for i, fill in enumerate((0, 7)):
        tm.clear()
        # another history in the ring: rounds of an unrelated record
        for _ in range(fill):
            with tm.span(tm.ROUND):
                tm.count(tm.CLIENT_STEPS_RUN, 99)
        tr = _trainer(data, engine="batched", wire_format="csr_q",
                      error_feedback=True,
                      checkpoint_dir=str(tmp_path / f"ckpt{i}"))
        for _ in range(2):
            tr.run_round()
        runs.append((tr.logs, _checkpoint_sections(tr)))
    (logs_a, ckpt_a), (logs_b, ckpt_b) = runs
    assert logs_a == logs_b
    assert ckpt_a and ckpt_a == ckpt_b


def test_scope_names_survive_the_persistent_compilation_cache(tmp_path):
    """An executable the cache holds from a program that differs only in
    its scope names is not handed back under the old names."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    x = jax.numpy.ones(8)

    def named(name):
        def f(v):
            with tm.scope(name):
                return jax.numpy.sin(v) * 2
        return f

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        paths = [_scope_paths(jax.jit(named(n)).lower(x).compile().as_text())
                 for n in (tm.UPLOAD, tm.FINALIZE)]
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert any(tmp_path.iterdir())
    assert paths == [{"", tm.UPLOAD}, {"", tm.FINALIZE}]
