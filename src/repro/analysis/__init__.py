"""Static-analysis passes over the repo's own source tree.

The analysis package is tooling *about* the reproduction, not part of the
runtime: it machine-checks the prose contracts of docs/DESIGN.md (one-lock
concurrency, JAX mesh-API shims, device residency, shard purity) so a
refactor cannot silently violate them. Everything here is stdlib-only —
the CI static-analysis job runs it without installing jax.
"""
