"""One module per engine the benchmark drives, named by a mix's `engine`."""
