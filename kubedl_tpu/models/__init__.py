"""The model definitions. Importing the package itself loads no JAX."""

# The named configurations (`LlamaConfig.config_for`, and the trainer's
# `--model` choices, which are parsed before JAX is imported): each name is
# a `LlamaConfig` factory's, its dashes and dots read as underscores.
PRESETS = ("tiny", "bench-150m", "bench-1b", "llama-7b", "lfm2-8b-a1b",
           "ouro-2.6b", "granite-4.0-h-micro", "xing4.0-29b-a4b",
           "trinity-large-preview")
