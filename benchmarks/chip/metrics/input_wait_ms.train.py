"""Host milliseconds per step spent making the batch
(``data.pipeline.global_batch``) and placing it (``jax.device_put``), in
the harness's ``bench.batch`` span, averaged over the window's steps."""


def read(rec, trace):
    spans = rec.get("batch_s") or []
    return 1e3 * sum(spans) / len(spans) if spans else None
