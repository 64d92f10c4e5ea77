"""Share of the prompt tokens admitted in the window that were mapped
onto a donor's or a pinned row's pages instead of being prefilled.

From the engine's counters: ``prefix_shared_tokens`` counts every
mapped token once (a pinned hit adds to ``pin_hit_tokens`` as well,
which is a subset and is not added again); ``prompt_tokens`` counts the
tokens fed to prefill lanes."""


def read(run):
    shared = run.delta("prefix_shared_tokens")
    fed = run.delta("prompt_tokens")
    return shared / (shared + fed) if shared + fed else None
