"""One module per client model: the program's loss for it, the weights
made from the seed, a plain reference of its loss, and its work counts."""
