"""Shared by the readers of the parts every model family names the same
way (horovod_tpu/common/tracing.py, docs/tracing.md "Under jit"): a
block's dense feed-forward `hvd.mlp`, the norms `hvd.norm`, the
embedding `hvd.embed`. Each scope is entered at the call site of its
module, so a reader keys on the role and not on a module's name (`ln1`,
`attn_norm`, `norm_h` are all `hvd.norm`). The readers sum the ops under
a scope with `_scopes.ms_per_step`, which reads the traced run's file
once for them all and gives None for a program without the scope (the
parent of the PR that added it): the metric is left out."""

# The program's vocabulary, copied (tests/benchmarking compares).
MLP = "hvd.mlp"
NORM = "hvd.norm"
EMBED = "hvd.embed"
