"""One module per `model_type` of a configuration file, found by that name:
what the program needs to build the model (`program_config`), the sizes
the cost functions take (`shapes`), and the plain reference's forward
(`reference_logits`) and what `reference/train_ref.follow` needs to take
the first training steps (`reference_steps`). A configuration of a new
family brings its own adapter (and reference) file."""
