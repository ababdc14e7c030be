"""Plain references: the published equations in straightforward
``jax.numpy``, float32 at ``highest`` matmul precision, no kernels. They
import nothing of ``petastorm_tpu`` and take nothing it has made: weights
come from the seed by the configuration's stated init, batches from the
stored bytes."""
