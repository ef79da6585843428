"""The benchmark's own tests run on the CPU at tiny sizes: the sizes are
passed by the tests, never by an option of the program or the harness."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
