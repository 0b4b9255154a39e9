"""Host time of the exact float32 rerank, ms per query reranked."""
from benchlib import readers


def read(run):
    return readers.self_ms(run, "rerank")
