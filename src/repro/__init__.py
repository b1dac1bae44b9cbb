"""repro: a JAX reproduction of Theano-MPI grown toward production scale."""
