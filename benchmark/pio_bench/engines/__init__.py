"""One module per engine family a configuration can name in its ``engine``
key.  A family knows how to put a configuration's model behind the
program's normal entry points and how to audit what came back."""
