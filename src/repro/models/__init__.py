"""Model layers: the GCN/GAT layers (``layers``), attention and MoE blocks,
and the language-model families behind ``registry.get_model``."""
