"""One module per model architecture, named by a configuration file's
`arch` key; `dense.py` states the contract each module meets."""
