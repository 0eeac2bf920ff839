"""Operation and byte counts of the model and of each kernel, from
shapes alone. One module a kernel, found by name from a metric's file."""
