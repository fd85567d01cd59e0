"""The dedicated preprocessing executor bounds duplicate tokenizer loads.

HfTokenizer keeps one underlying tokenizer per THREAD (PyO3 "Already
borrowed"), so the number of AutoTokenizer.from_pretrained calls equals the
number of distinct threads preprocessing ever runs on. The HTTP service now
runs preprocessing on llm.tokenizer.preprocessing_executor() — a small fixed
pool — instead of the default executor's unbounded thread set (ADVICE r5)."""

import sys
import threading
import types
from concurrent.futures import wait

from dynamo_tpu.llm.tokenizer import HfTokenizer, preprocessing_executor


def test_preprocessing_executor_is_small_and_shared():
    pool = preprocessing_executor()
    assert pool is preprocessing_executor()  # one process-wide pool
    assert pool._max_workers <= 4

    names = set()
    barrier_done = threading.Event()

    def job(_):
        names.add(threading.current_thread().name)
        return 1

    futs = [pool.submit(job, i) for i in range(64)]
    wait(futs)
    barrier_done.set()
    assert len(names) <= 4
    assert all(n.startswith("dyntpu-preproc") for n in names)


def test_thread_local_tokenizer_loads_bounded_by_pool(monkeypatch):
    """Drive an HfTokenizer from the preprocessing pool with a stubbed
    transformers module and count from_pretrained calls: at most one per pool
    worker (+1 for the construction-time instance's thread)."""
    loads = []

    class _FakeTok:
        eos_token_id = 2

        def __len__(self):
            return 100

        def encode(self, text, add_special_tokens=False):
            return [1, 2, 3]

    class _AutoTokenizer:
        @staticmethod
        def from_pretrained(path):
            loads.append(threading.current_thread().name)
            return _FakeTok()

    fake = types.ModuleType("transformers")
    fake.AutoTokenizer = _AutoTokenizer
    monkeypatch.setitem(sys.modules, "transformers", fake)

    tok = HfTokenizer("/does/not/matter")
    pool = preprocessing_executor()
    futs = [pool.submit(tok.encode, "hello") for _ in range(64)]
    wait(futs)
    for f in futs:
        assert f.result() == [1, 2, 3]
    # construction thread + at most one load per pool worker
    assert len(loads) <= 1 + pool._max_workers



def test_the_tokenizer_import_looks_for_no_framework(monkeypatch):
    """`HfTokenizer` tells transformers not to look for torch, TensorFlow or
    Flax before it imports it (the look imports them: 20 s of a server's
    start), and leaves a value the user set alone."""
    import os

    fake = types.ModuleType("transformers")
    fake.AutoTokenizer = types.SimpleNamespace(from_pretrained=lambda path: [0, 1])
    monkeypatch.setitem(sys.modules, "transformers", fake)
    monkeypatch.delenv("USE_TORCH", raising=False)
    monkeypatch.delenv("USE_FLAX", raising=False)
    monkeypatch.setenv("USE_TF", "1")
    tok = HfTokenizer.__new__(HfTokenizer)
    tok._path, tok._local = "/does/not/matter", threading.local()
    assert tok._tok == [0, 1]
    assert (os.environ["USE_TORCH"], os.environ["USE_FLAX"], os.environ["USE_TF"]) == ("0", "0", "1")
