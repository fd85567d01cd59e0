"""Disaggregated prefill/decode serving (reference: docs/disagg_serving.md,
examples/llm/components/{worker,prefill_worker}.py, the NIXL patch)."""



def refuse_recurrent(engine, role: str) -> None:
    """Disaggregation ships a prompt's KV pages from a prefill worker to a
    decode worker. A model with recurrent layers keeps per-slot state beside
    the pages, and that state has no wire form yet: refuse at start-up."""
    if engine.model is not None and engine.model.recurrent:  # None: not loaded yet
        raise ValueError(
            f"{role} is refused for {type(engine.model).__name__}: the model has "
            "recurrent layers, whose per-slot state would have to travel with "
            "the KV pages, and state snapshots are not built"
        )


from dynamo_tpu.disagg.decode_worker import DisaggDecodeEngine  # noqa: E402
from dynamo_tpu.disagg.prefill_worker import PrefillWorker  # noqa: E402
