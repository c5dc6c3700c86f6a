"""Plain references, one module per architecture, named by each
configuration's ``"reference"`` key. They import nothing of the program."""
