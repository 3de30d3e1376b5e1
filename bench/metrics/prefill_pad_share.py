"""Share of the prompt-slab positions the prefill waves computed over the
window that held no prompt token: 1 - ``serve_prefill_tokens_total`` /
``serve_prefill_slab_tokens_total`` (the program's counters)."""


def read(run):
    slab = run.counters.get("serve_prefill_slab_tokens_total", 0.0)
    if not slab:
        return None
    return 100.0 * (1.0 - run.counters["serve_prefill_tokens_total"] / slab)
