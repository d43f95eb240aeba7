"""Plain references of the zoo's newer models: the forward pass, loss
and (by ``jax.grad``) gradients in straightforward ``jax.numpy``,
float32, no kernel, written from each model's published equations.
The tests and the chip checks hold the fluid programs to them; they
import nothing of the code they check."""
