"""A cell cut to a size the CPU runs in seconds: every width, the depth,
the latent and the batch made small.  Used by the tests, and on the chip
to record the small trace the tests read (``record_trace.py``)."""


def shrink(config, traffic):
    """``edit`` hook for ``bench.harness.measure``."""
    config["run"]["arch_overrides"].update(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=128, vocab_size=256)
    config["run"]["data"]["encoder"].update(cond_dim=32, cond_len=4,
                                            vocab=256, hidden=64)
    config["run"]["dist"]["microbatch"] = 2
    traffic.update(num_steps=2, group_size=2, batch_prompts=2,
                   latent_tokens=16, latent_dim=8, prompt_pool=16)
