"""What the readers of the Mamba-2 / attention / experts cell share: how its
sizes are known, and how its run is handed to an accepted reader that
computes the same thing. No metric of its own. Ops are told apart as
`hybrid_ops.py` says."""


def sizes_of(run):
  """The run's sizes if they are this configuration's, else None."""
  sizes = run.get("sizes") or {}
  return sizes if "mamba_num_heads" in sizes else None


def as_hybrid(run):
  """This cell's run as the accepted hybrid decoder's readers take one. They
  know a hybrid decoder's run by `linear_num_value_heads` among its sizes
  (`hybrid_ops.sizes_of`) and read, of the sizes, only names both
  configurations have (`sequence_length`, `hidden_size`, `router_width`,
  `num_experts`, `num_experts_per_tok`, `expert_buffer_factor`,
  `num_attention_heads`, `head_dim`). Any other run comes back empty: they
  find nothing in it."""
  sizes = sizes_of(run)
  return dict(run, sizes=dict(sizes, linear_num_value_heads=None)
              ) if sizes else {}
