"""Plain host references of the semantics the program promises.

NumPy only: nothing here imports the program, and nothing takes what the
program made.  `controls` holds the same semantics with one guarantee
broken, in JAX, to be put in the program's place.
"""
