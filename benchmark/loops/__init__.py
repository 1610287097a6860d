"""One driver loop per traffic `kind`; a traffic file's `kind` names the
module here that runs it."""
