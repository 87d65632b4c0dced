"""Host-clock milliseconds per prefilled chunk: ``GenStats.prefill_s`` over
the chunks prefilled (restored chunks excluded), every request of the window."""


def read(d):
    if d.get("kind") != "serve":
        return None
    chunks = sum(st.n_chunks - st.chunks_skipped for st in d["stats"])
    if not chunks:
        return None
    return 1e3 * sum(st.prefill_s for st in d["stats"]) / chunks
