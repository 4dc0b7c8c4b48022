from repro.models.transformer import (  # noqa: F401
    init_params,
    forward_train,
    forward_prefill,
    forward_decode,
    forward_chunk,
    prefill_chunk_len,
    init_cache,
    expert_counts,
)
