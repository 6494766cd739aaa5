"""load generator: create requests re-sent on the client's timeout ladder in the window (count)."""
from benchmarks.harness import readers

read = readers.client_retries
