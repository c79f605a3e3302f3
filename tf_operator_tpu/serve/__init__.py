"""Serving subsystem (r10): continuous-batching LM decode under the
operator. ``kvcache`` — the paged KV pool + free-list allocator;
``engine`` — the iteration-level (continuous-batching) scheduler loop;
``spec`` — serve TPUJob construction (the CLI / probe seam)."""
