"""Plain float32 references, one module per model family.  They import
nothing of the program under test: each builds its weights from the seed
itself, in the program's parameter layout, and computes the loss with
straightforward jax.numpy."""
