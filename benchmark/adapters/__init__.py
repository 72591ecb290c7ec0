"""One module per `model_type` of a configuration file, found by that name:
what the program needs to build the model (`program_config`), the sizes
the cost functions take (`shapes`), and the plain reference's forward
(`reference_logits`) and what `reference/train_ref.follow` needs to take
the first training steps (`reference_steps`). A family says what else it
has by functions of its own, which `model_config` looks up and the readers
ask, so that no reader names a model's key: `train_flops_per_token(conf,
seq)` where its block is not the homogeneous dense one, `expert_layer(conf)`
(held, published, per_token, hidden, width of ONE expert) where it routes
to experts, `attention_window(conf)` where some layers see a window. A
configuration of a new family brings its own adapter (and reference) file."""
