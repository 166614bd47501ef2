# upstream examples/escaping.py: gnarly strings survive unmangled.
tricky = "quotes: ' \" backtick: ` dollar: $HOME newline-escape: \\n brace: {x}"
print(tricky)
print(f"f-string ok: {1 + 1}")
