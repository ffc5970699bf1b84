// Copyright (c) 2026, nds-tpu authors. Licensed under the Apache License, Version 2.0.
//
// ndsgen: native TPC-DS-style raw data generator for the nds-tpu framework.
//
// Plays the role dsdgen plays in the reference harness (driven per-chunk by
// nds_gen_data.py; ref: nds/nds_gen_data.py:183-244 and the MR wrapper
// nds/tpcds-gen/src/main/java/org/notmysock/tpcds/GenTable.java:188-209):
// emits '|'-delimited flat files per table with dsdgen-compatible naming
// (<table>_<child>_<parallel>.dat) and CLI flags (-scale/-parallel/-child/
// -table/-update/-rngseed/-dir).
//
// Design: every field of every row is a pure function of
// (rngseed, table, row, column) via splitmix64 mixing, so any chunk of any
// table can be generated independently with no cross-chunk or cross-table
// state. Returns re-derive their originating sale row's fields from the same
// hash stream, giving referential integrity (matching ticket/order numbers,
// item_sks and consistent amounts) without coordination. This is what makes
// distributed generation embarrassingly parallel across pod hosts.
//
// NOTE: this generator produces spec-shaped, query-meaningful data (real
// calendar, enumerated demographics, consistent pricing chains, SCD dims),
// not bit-identical dsdgen output. For bit-parity with reference data the
// harness honours $TPCDS_HOME and drives the patched TPC-DS C toolkit
// instead (see nds_tpu/check.py:check_build_ndsgen).

#include <cinttypes>
#include <cmath>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <vector>

// ---------------------------------------------------------------------------
// Hashing / RNG: stateless splitmix64 over (seed, table, row, col)
// ---------------------------------------------------------------------------

static uint64_t g_seed = 19620718ULL;  // default rngseed

static inline uint64_t splitmix64(uint64_t x) {
  x += 0x9E3779B97F4A7C15ULL;
  x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ULL;
  x = (x ^ (x >> 27)) * 0x94D049BB133111EBULL;
  return x ^ (x >> 31);
}

static inline uint64_t h4(uint64_t table, uint64_t row, uint64_t col) {
  uint64_t x = g_seed;
  x = splitmix64(x ^ (table * 0xA24BAED4963EE407ULL));
  x = splitmix64(x ^ (row * 0x9FB21C651E98DF25ULL));
  x = splitmix64(x ^ (col * 0xD6E8FEB86659FD93ULL));
  return x;
}

// uniform integer in [lo, hi] inclusive
static inline int64_t uni(uint64_t t, uint64_t r, uint64_t c, int64_t lo, int64_t hi) {
  return lo + (int64_t)(h4(t, r, c) % (uint64_t)(hi - lo + 1));
}

// null decision: true => emit NULL. pct in [0,100]
static inline bool isnull(uint64_t t, uint64_t r, uint64_t c, int pct) {
  return (int)(h4(t, r, c ^ 0x5A5A5A5AULL) % 100) < pct;
}

// ---------------------------------------------------------------------------
// Calendar (Howard Hinnant's civil-days algorithms, public domain technique)
// ---------------------------------------------------------------------------

static constexpr int64_t kJulianEpoch = 2440588;  // julian day of 1970-01-01
static constexpr int64_t kDateSkLo = 2415022;     // 1900-01-02, first d_date_sk
static constexpr int64_t kDateDimRows = 73049;    // through 2100-01-01
static constexpr int64_t kSalesDateLo = 2450816;  // 1998-01-02 (5y sales window)
static constexpr int64_t kSalesDateHi = 2452642;  // 2002-12-31

static int64_t days_from_civil(int y, int m, int d) {
  y -= m <= 2;
  const int64_t era = (y >= 0 ? y : y - 399) / 400;
  const unsigned yoe = (unsigned)(y - era * 400);
  const unsigned doy = (153 * (m + (m > 2 ? -3 : 9)) + 2) / 5 + d - 1;
  const unsigned doe = yoe * 365 + yoe / 4 - yoe / 100 + doy;
  return era * 146097 + (int64_t)doe - 719468;
}

static void civil_from_days(int64_t z, int* yy, int* mm, int* dd) {
  z += 719468;
  const int64_t era = (z >= 0 ? z : z - 146096) / 146097;
  const unsigned doe = (unsigned)(z - era * 146097);
  const unsigned yoe = (doe - doe / 1460 + doe / 36524 - doe / 146096) / 365;
  const int64_t y = (int64_t)yoe + era * 400;
  const unsigned doy = doe - (365 * yoe + yoe / 4 - yoe / 100);
  const unsigned mp = (5 * doy + 2) / 153;
  const unsigned d = doy - (153 * mp + 2) / 5 + 1;
  const unsigned m = mp + (mp < 10 ? 3 : -9);
  *yy = (int)(y + (m <= 2));
  *mm = (int)m;
  *dd = (int)d;
}

static inline void jday_to_civil(int64_t jday, int* y, int* m, int* d) {
  civil_from_days(jday - kJulianEpoch, y, m, d);
}

static inline int64_t civil_to_jday(int y, int m, int d) {
  return days_from_civil(y, m, d) + kJulianEpoch;
}

// 0 = Sunday ... 6 = Saturday
static inline int dow_of_jday(int64_t jday) {
  int64_t z = jday - kJulianEpoch;  // 1970-01-01 was a Thursday (4)
  return (int)(((z % 7) + 7 + 4) % 7);
}

// ---------------------------------------------------------------------------
// Row writer: buffered '|'-delimited output with trailing delimiter
// (dsdgen-compatible; readers strip the trailing empty field)
// ---------------------------------------------------------------------------

struct Row {
  FILE* f;
  explicit Row(FILE* file) : f(file) {}
  void raw(const char* s) { fputs(s, f); fputc('|', f); }
  void nul() { fputc('|', f); }
  void i(int64_t v, bool null = false) { if (null) { nul(); return; } fprintf(f, "%" PRId64 "|", v); }
  void i_or_null(int64_t v, bool null) { if (null) nul(); else i(v); }
  void dec(int64_t cents, bool null = false) {
    if (null) { nul(); return; }
    bool neg = cents < 0;
    if (neg) cents = -cents;
    fprintf(f, "%s%" PRId64 ".%02" PRId64 "|", neg ? "-" : "", cents / 100, cents % 100);
  }
  void s(const std::string& v, bool null = false) { if (null) nul(); else raw(v.c_str()); }
  void date(int64_t jday, bool null = false) {
    if (null) { nul(); return; }
    int y, m, d;
    jday_to_civil(jday, &y, &m, &d);
    fprintf(f, "%04d-%02d-%02d|", y, m, d);
  }
  void end() { fputc('\n', f); }
};

// 16-char business key: base-26 encoding of sk, 'A'-padded (dsdgen-style
// AAAA...X ids). Deterministic so s_* refresh tables can reference dims.
static std::string id16(int64_t sk) {
  char buf[17];
  memset(buf, 'A', 16);
  buf[16] = 0;
  uint64_t v = (uint64_t)sk;
  int pos = 15;
  while (v > 0 && pos >= 0) {
    buf[pos--] = (char)('A' + (v % 26));
    v /= 26;
  }
  return std::string(buf);
}

static std::string date_str(int64_t jday) {
  int y, m, d;
  jday_to_civil(jday, &y, &m, &d);
  char buf[16];
  snprintf(buf, sizeof buf, "%04d-%02d-%02d", y, m, d);
  return std::string(buf);
}

// ---------------------------------------------------------------------------
// Value pools
// ---------------------------------------------------------------------------

#define POOL(name, ...) static const char* name[] = {__VA_ARGS__}; \
  static const int name##_n = (int)(sizeof(name) / sizeof(name[0]))

POOL(kStreetNames, "Main", "Oak", "Park", "Elm", "First", "Second", "Cedar", "Pine", "Maple",
     "Lake", "Hill", "Walnut", "Spring", "North", "Ridge", "Church", "Willow", "Mill", "Sunset",
     "Railroad", "Jackson", "River", "Highland", "Johnson", "Dogwood", "Chestnut", "Spruce",
     "Wilson", "Meadow", "Forest", "Broadway", "Franklin", "Smith", "College", "Washington");
POOL(kStreetTypes, "Street", "Ave", "Blvd", "Road", "Lane", "Court", "Drive", "Circle",
     "Parkway", "Way", "Pkwy", "Ct", "Dr", "Ln", "RD", "ST", "Boulevard", "Wy", "Cir", "Avenue");
POOL(kCities, "Midway", "Fairview", "Oak Grove", "Five Points", "Oakland", "Riverside",
     "Salem", "Georgetown", "Franklin", "New Hope", "Bunker Hill", "Hopewell", "Antioch",
     "Concord", "Clifton", "Marion", "Springfield", "Greenville", "Bridgeport", "Oakdale",
     "Glendale", "Lakeview", "Centerville", "Mount Olive", "Union", "Glenwood", "Pleasant Hill",
     "Liberty", "Sulphur Springs", "Pine Grove", "Waterloo", "Edgewood", "Friendship",
     "Greenwood", "Deerfield", "Shiloh", "Mountain View", "Lakewood", "Summit", "Plainview",
     "Pleasant Valley", "Woodville", "White Oak", "Oakwood", "Harmony", "Highland Park",
     "Kingston", "Red Hill", "Enterprise", "Arlington", "Lebanon", "Clinton", "Spring Hill",
     "Buena Vista", "Newport", "Florence", "Jamestown", "Ashland", "Wildwood", "Macedonia");
POOL(kCounties, "Williamson County", "Walker County", "Ziebach County", "Daviess County",
     "Barrow County", "Franklin Parish", "Luce County", "Richland County", "Furnas County",
     "Maverick County", "Huron County", "Kittitas County", "Mobile County", "Fairfield County",
     "Jackson County", "Dauphin County", "San Miguel County", "Pennington County",
     "Bronx County", "Orange County", "Perry County", "Halifax County", "Dona Ana County",
     "Gogebic County", "Lea County", "Mesa County", "Wadena County", "Pipestone County");
POOL(kStates, "AL", "AK", "AZ", "AR", "CA", "CO", "CT", "DE", "FL", "GA", "HI", "ID", "IL",
     "IN", "IA", "KS", "KY", "LA", "ME", "MD", "MA", "MI", "MN", "MS", "MO", "MT", "NE", "NV",
     "NH", "NJ", "NM", "NY", "NC", "ND", "OH", "OK", "OR", "PA", "RI", "SC", "SD", "TN", "TX",
     "UT", "VT", "VA", "WA", "WV", "WI", "WY");
POOL(kCountries, "United States");
POOL(kLocTypes, "apartment", "condo", "single family");
POOL(kEducation, "Primary", "Secondary", "College", "2 yr Degree", "4 yr Degree",
     "Advanced Degree", "Unknown");
POOL(kMarital, "M", "S", "D", "W", "U");
POOL(kCredit, "Low Risk", "Good", "High Risk", "Unknown");
POOL(kBuyPotential, ">10000", "5001-10000", "1001-5000", "501-1000", "0-500", "Unknown");
POOL(kDayNames, "Sunday", "Monday", "Tuesday", "Wednesday", "Thursday", "Friday", "Saturday");
POOL(kShipTypes, "EXPRESS", "NEXT DAY", "OVERNIGHT", "REGULAR", "TWO DAY");
POOL(kShipCodes, "AIR", "SURFACE", "SEA", "MSC");
POOL(kCarriers, "UPS", "FEDEX", "AIRBORNE", "USPS", "DHL", "TBS", "ZHOU", "ZOUROS", "MSC",
     "LATVIAN", "ALLIANCE", "GREAT EASTERN", "DIAMOND", "RUPEKSA", "ORIENTAL", "BOXBUNDLES",
     "GERMA", "HARMSTORF", "PRIVATECARRIER", "STERLING");
POOL(kReasons, "Package was damaged", "Stopped working", "Did not get it on time",
     "Not the product that was ordred", "Parts missing", "Does not work with a product that "
     "I have", "Gift exchange", "Did not like the color", "Did not like the model",
     "Did not like the make", "Did not like the warranty", "No service location in my area",
     "Found a better price in a store", "Found a better extended warranty in a store",
     "Did not fit", "Wrong size", "Lost my job", "unauthoized purchase", "duplicate purchase",
     "its is a boy", "its is a girl", "i do not like it", "reason 23", "reason 24",
     "reason 25", "reason 26", "reason 27", "reason 28", "reason 29", "reason 30",
     "reason 31", "reason 32", "reason 33", "reason 34", "reason 35");
POOL(kCategories, "Women", "Men", "Children", "Sports", "Music", "Books", "Home",
     "Electronics", "Jewelry", "Shoes");
POOL(kClasses, "accessories", "fragrances", "dresses", "pants", "swimwear", "maternity",
     "shirts", "sports-apparel", "infants", "toddlers", "school-uniforms", "athletic",
     "baseball", "basketball", "camping", "fishing", "football", "golf", "hockey", "optics",
     "pools", "sailing", "tennis", "classical", "country", "pop", "rock", "arts", "business",
     "computers", "cooking", "entertainments", "fiction", "history", "home repair", "mystery",
     "parenting", "reference", "romance", "science", "self-help", "sports", "travel",
     "bathroom", "bedding", "blinds/shades", "curtains/drapes", "decor", "flatware",
     "furniture", "glassware", "kids", "lighting", "mattresses", "paint", "rugs", "tables",
     "wallpaper", "audio", "automotive", "cameras", "camcorders", "dvd/vcr players",
     "karoke", "memory", "monitors", "musical", "personal", "portable", "scanners",
     "stereo", "televisions", "wireless", "birdal", "costume", "diamonds", "earings",
     "estate", "gold", "jewelry boxes", "loose stones", "mens watch", "pendants", "rings",
     "semi-precious", "womens watch", "athletic shoes", "kids shoes", "mens shoes", "womens");
POOL(kColors, "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
     "blanched", "blue", "blush", "brown", "burlywood", "burnished", "chartreuse",
     "chiffon", "chocolate", "coral", "cornflower", "cornsilk", "cream", "cyan", "dark",
     "deep", "dim", "dodger", "drab", "firebrick", "floral", "forest", "frosted",
     "gainsboro", "ghost", "goldenrod", "green", "grey", "honeydew", "hot", "indian",
     "ivory", "khaki", "lace", "lavender", "lawn", "lemon", "light", "lime", "linen",
     "magenta", "maroon", "medium", "metallic", "midnight", "mint", "misty", "moccasin",
     "navajo", "navy", "olive", "orange", "orchid", "pale", "papaya", "peach", "peru",
     "pink", "plum", "powder", "puff", "purple", "red", "rose", "rosy", "royal", "saddle",
     "salmon", "sandy", "seashell", "sienna", "sky", "slate", "smoke", "snow", "spring",
     "steel", "tan", "thistle", "tomato", "turquoise", "violet", "wheat", "white", "yellow");
POOL(kUnits, "Each", "Dozen", "Case", "Pallet", "Gross", "Box", "Bundle", "Tsp", "Oz",
     "Lb", "Ton", "Dram", "Cup", "Gram", "Pound", "Ounce", "Unknown", "Carton", "Bunch", "N/A");
POOL(kSizes, "small", "medium", "large", "extra large", "economy", "N/A", "petite");
POOL(kHours, "8AM-8AM", "8AM-4PM", "8AM-12AM");
POOL(kFirstNames, "James", "Mary", "John", "Patricia", "Robert", "Jennifer", "Michael",
     "Linda", "William", "Elizabeth", "David", "Barbara", "Richard", "Susan", "Joseph",
     "Jessica", "Thomas", "Sarah", "Charles", "Karen", "Christopher", "Nancy", "Daniel",
     "Lisa", "Matthew", "Margaret", "Anthony", "Betty", "Donald", "Sandra", "Mark",
     "Ashley", "Paul", "Dorothy", "Steven", "Kimberly", "Andrew", "Emily", "Kenneth",
     "Donna", "Joshua", "Michelle", "George", "Carol", "Kevin", "Amanda", "Brian",
     "Melissa", "Edward", "Deborah", "Ronald", "Stephanie", "Timothy", "Rebecca", "Jason",
     "Laura", "Jeffrey", "Sharon", "Ryan", "Cynthia", "Jacob", "Kathleen", "Gary", "Amy",
     "Nicholas", "Shirley", "Eric", "Angela", "Jonathan", "Helen", "Stephen", "Anna",
     "Larry", "Brenda", "Justin", "Pamela", "Scott", "Nicole", "Brandon", "Ruth");
POOL(kLastNames, "Smith", "Johnson", "Williams", "Brown", "Jones", "Garcia", "Miller",
     "Davis", "Rodriguez", "Martinez", "Hernandez", "Lopez", "Gonzalez", "Wilson",
     "Anderson", "Thomas", "Taylor", "Moore", "Jackson", "Martin", "Lee", "Perez",
     "Thompson", "White", "Harris", "Sanchez", "Clark", "Ramirez", "Lewis", "Robinson",
     "Walker", "Young", "Allen", "King", "Wright", "Scott", "Torres", "Nguyen", "Hill",
     "Flores", "Green", "Adams", "Nelson", "Baker", "Hall", "Rivera", "Campbell",
     "Mitchell", "Carter", "Roberts", "Gomez", "Phillips", "Evans", "Turner", "Diaz");
POOL(kSalutationsM, "Mr.", "Dr.", "Sir");
POOL(kSalutationsF, "Mrs.", "Ms.", "Miss", "Dr.");
POOL(kBirthCountries, "UNITED STATES", "CANADA", "MEXICO", "BRAZIL", "GERMANY", "FRANCE",
     "UNITED KINGDOM", "ITALY", "SPAIN", "JAPAN", "CHINA", "INDIA", "AUSTRALIA", "RUSSIA",
     "NETHERLANDS", "GREECE", "TURKEY", "EGYPT", "NIGERIA", "KENYA", "ARGENTINA", "CHILE",
     "PERU", "COLOMBIA", "VENEZUELA", "PORTUGAL", "SWEDEN", "NORWAY", "FINLAND", "DENMARK",
     "POLAND", "HUNGARY", "ROMANIA", "BULGARIA", "THAILAND", "VIETNAM", "PHILIPPINES",
     "INDONESIA", "MALAYSIA", "SINGAPORE", "NEW ZEALAND", "SOUTH AFRICA", "MOROCCO",
     "ALGERIA", "TUNISIA", "ISRAEL", "JORDAN", "IRAQ", "PAKISTAN", "BANGLADESH");
POOL(kWords, "bar", "ought", "able", "pri", "pres", "ese", "anti", "cally", "ation", "eing",
     "ideas", "things", "systems", "results", "members", "children", "questions", "services",
     "countries", "problems", "hands", "parts", "groups", "cases", "women", "interests",
     "companies", "times", "levels", "areas", "markets", "activities", "conditions", "eyes",
     "sales", "figures", "others", "certain", "national", "different", "important", "local",
     "major", "available", "special", "particular", "general", "significant", "recent",
     "natural", "individual", "various", "central", "similar", "necessary", "actual");
POOL(kPromoNames, "ought", "able", "pri", "pres", "ese", "anti", "cally", "ation", "eing",
     "bar");
POOL(kMealTimes, "breakfast", "lunch", "dinner");
POOL(kShifts, "first", "second", "third");
POOL(kSubShifts, "morning", "afternoon", "evening", "night");
POOL(kDepartments, "DEPARTMENT");
POOL(kCatalogTypes, "monthly", "quarterly", "bi-annual");
POOL(kWebTypes, "welcome", "protected", "dynamic", "feedback", "general", "ad", "order");
POOL(kDivNames, "ought", "able", "pri", "pres", "ese", "anti", "cally", "ation", "eing",
     "bar", "ought able", "pri ese");
POOL(kMktClasses, "A bit narrow forces matter.", "Architects survive to a ways.",
     "Political viewers develop for a styles.", "Domestic rates must not lead very.",
     "Large levels show home, final thin", "Significant members might call.",
     "Previous counties ought to approve.", "Alive situations strike o",
     "Tall sources use quite wrong directors.", "New players sell most n");

static const char* pick(const char** pool, int n, uint64_t t, uint64_t r, uint64_t c) {
  return pool[h4(t, r, c) % (uint64_t)n];
}
#define PK(pool, t, r, c) pick(pool, pool##_n, t, r, c)

// Scale-banded state vocabulary, shared with the query sampler
// (nds_tpu/queries/__init__.py active_states — keep the bands in sync).
// The TPC-DS toolkit's fips_county distribution plays the same role: at
// small scales both dsdgen rows and dsqgen substitutions draw from the same
// reduced state set, so state-predicate queries stay non-degenerate.
static int states_active(double sf) {
  if (sf < 1.0) return 8;
  if (sf < 100.0) return 16;
  if (sf < 1000.0) return 32;
  return 50;
}

// same banding idea for the other geographic vocabularies (city/county);
// capped by each pool's size
static int geo_active(double sf, int pool_n) {
  return std::min(pool_n, states_active(sf));
}

// word-salad sentence for descriptions/comments
static std::string sentence(uint64_t t, uint64_t r, uint64_t c, int maxwords) {
  int n = 3 + (int)(h4(t, r, c ^ 0x77ULL) % (uint64_t)(maxwords - 2));
  std::string out;
  for (int i = 0; i < n; i++) {
    if (i) out += ' ';
    out += kWords[h4(t, r, c + 100 + i) % (uint64_t)kWords_n];
  }
  return out;
}

// ---------------------------------------------------------------------------
// Table ids + row-count scaling
// ---------------------------------------------------------------------------

enum TableId {
  T_CUSTOMER_ADDRESS, T_CUSTOMER_DEMOGRAPHICS, T_DATE_DIM, T_WAREHOUSE, T_SHIP_MODE,
  T_TIME_DIM, T_REASON, T_INCOME_BAND, T_ITEM, T_STORE, T_CALL_CENTER, T_CUSTOMER,
  T_WEB_SITE, T_STORE_RETURNS, T_HOUSEHOLD_DEMOGRAPHICS, T_WEB_PAGE, T_PROMOTION,
  T_CATALOG_PAGE, T_INVENTORY, T_CATALOG_RETURNS, T_WEB_RETURNS, T_WEB_SALES,
  T_CATALOG_SALES, T_STORE_SALES,
  // refresh (-update) tables
  T_S_PURCHASE, T_S_PURCHASE_LINEITEM, T_S_CATALOG_ORDER, T_S_CATALOG_ORDER_LINEITEM,
  T_S_WEB_ORDER, T_S_WEB_ORDER_LINEITEM, T_S_STORE_RETURNS, T_S_CATALOG_RETURNS,
  T_S_WEB_RETURNS, T_S_INVENTORY, T_DELETE, T_INVENTORY_DELETE,
  T_MAX
};

static const char* kTableNames[T_MAX] = {
  "customer_address", "customer_demographics", "date_dim", "warehouse", "ship_mode",
  "time_dim", "reason", "income_band", "item", "store", "call_center", "customer",
  "web_site", "store_returns", "household_demographics", "web_page", "promotion",
  "catalog_page", "inventory", "catalog_returns", "web_returns", "web_sales",
  "catalog_sales", "store_sales",
  "s_purchase", "s_purchase_lineitem", "s_catalog_order", "s_catalog_order_lineitem",
  "s_web_order", "s_web_order_lineitem", "s_store_returns", "s_catalog_returns",
  "s_web_returns", "s_inventory", "delete", "inventory_delete",
};

// Geometric interpolation over log10(scale) between anchor points at
// SF {1, 10, 100, 1000, 3000, 10000} — mirrors dsdgen's sublinear dimension
// scaling without reimplementing its internal tables.
static int64_t interp_rows(double sf, const double* anchors) {
  static const double pts[6] = {1, 10, 100, 1000, 3000, 10000};
  if (sf <= 1.0) return (int64_t)std::llround(anchors[0]);
  if (sf >= 10000) return (int64_t)anchors[5];
  int i = 0;
  while (i < 5 && sf > pts[i + 1]) i++;
  double t = (std::log10(sf) - std::log10(pts[i])) / (std::log10(pts[i + 1]) - std::log10(pts[i]));
  double v = anchors[i] * std::pow(anchors[i + 1] / anchors[i], t);
  return (int64_t)std::llround(v);
}

struct Scaling {
  double sf;
  int64_t rows[T_MAX];
  int64_t customers, addresses, items, stores, call_centers, web_sites, warehouses,
      web_pages, promotions, catalog_pages, reasons;
  int64_t ss_tickets, cs_orders, ws_orders;

  // item's distinct business keys: rows 2k and 2k+1 share item_id k+1
  int64_t item_ids() const { return (items + 1) / 2; }

  explicit Scaling(double sf_) : sf(sf_) {
    static const double aCust[6]   = {100e3, 500e3, 2e6, 12e6, 30e6, 65e6};
    static const double aItem[6]   = {18e3, 102e3, 204e3, 300e3, 360e3, 402e3};
    static const double aStore[6]  = {12, 102, 402, 1002, 1350, 1500};
    static const double aCC[6]     = {6, 12, 24, 30, 36, 42};
    static const double aWebSite[6]= {30, 36, 42, 48, 54, 60};
    static const double aWh[6]     = {5, 10, 15, 20, 22, 25};
    static const double aWebPage[6]= {60, 200, 2040, 3000, 3600, 4002};
    static const double aPromo[6]  = {300, 350, 1000, 1500, 1800, 2000};
    static const double aCatPage[6]= {11718, 12000, 20400, 30000, 36000, 40000};
    static const double aReason[6] = {35, 45, 55, 65, 67, 70};

    customers = std::max<int64_t>(1000, interp_rows(sf, aCust));
    if (sf < 1.0) customers = std::max<int64_t>(1000, (int64_t)(100e3 * sf));
    addresses = customers / 2;
    items = std::max<int64_t>(1000, sf < 1.0 ? (int64_t)(18e3 * (0.25 + 0.75 * sf)) : interp_rows(sf, aItem));
    stores = interp_rows(sf, aStore);
    call_centers = interp_rows(sf, aCC);
    web_sites = interp_rows(sf, aWebSite);
    warehouses = interp_rows(sf, aWh);
    web_pages = interp_rows(sf, aWebPage);
    promotions = interp_rows(sf, aPromo);
    catalog_pages = interp_rows(sf, aCatPage);
    reasons = interp_rows(sf, aReason);

    // facts: linear in SF; tickets/orders carry fixed line counts so that
    // per-row fields derive from (ticket, line) with no cross-row state
    ss_tickets = std::max<int64_t>(100, (int64_t)(240034.0 * sf));
    cs_orders  = std::max<int64_t>(100, (int64_t)(144155.0 * sf));
    ws_orders  = std::max<int64_t>(100, (int64_t)(59949.0 * sf));

    for (int i = 0; i < T_MAX; i++) rows[i] = 0;
    rows[T_CUSTOMER_ADDRESS] = addresses;
    rows[T_CUSTOMER_DEMOGRAPHICS] = 1920800;  // full enumeration, scale-invariant
    rows[T_DATE_DIM] = kDateDimRows;
    rows[T_WAREHOUSE] = warehouses;
    rows[T_SHIP_MODE] = 20;
    rows[T_TIME_DIM] = 86400;
    rows[T_REASON] = reasons;
    rows[T_INCOME_BAND] = 20;
    rows[T_ITEM] = items;
    rows[T_STORE] = stores;
    rows[T_CALL_CENTER] = call_centers;
    rows[T_CUSTOMER] = customers;
    rows[T_WEB_SITE] = web_sites;
    rows[T_HOUSEHOLD_DEMOGRAPHICS] = 7200;  // 20*6*10*6 enumeration
    rows[T_WEB_PAGE] = web_pages;
    rows[T_PROMOTION] = promotions;
    rows[T_CATALOG_PAGE] = catalog_pages;
    rows[T_STORE_SALES] = ss_tickets * 12;
    rows[T_CATALOG_SALES] = cs_orders * 10;
    rows[T_WEB_SALES] = ws_orders * 12;
    rows[T_STORE_RETURNS] = rows[T_STORE_SALES] / 10;
    rows[T_CATALOG_RETURNS] = rows[T_CATALOG_SALES] / 10;
    rows[T_WEB_RETURNS] = rows[T_WEB_SALES] / 10;
    // weekly inventory snapshots over the 5-year sales window; sub-SF1 test
    // scales shrink the window so inventory stays proportionate. One row a
    // distinct item_id, as dsdgen counts them (SCD pairs share one id): SF1
    // holds 261 * 5 * 9000 = 11,745,000 rows
    int64_t inv_weeks = sf >= 1.0 ? 261 : std::max<int64_t>(13, (int64_t)(261 * sf * 10));
    rows[T_INVENTORY] = inv_weeks * warehouses * item_ids();
    // refresh set: ~0.1% of the base facts per update
    rows[T_S_PURCHASE] = std::max<int64_t>(10, ss_tickets / 1000);
    rows[T_S_PURCHASE_LINEITEM] = rows[T_S_PURCHASE] * 12;
    rows[T_S_CATALOG_ORDER] = std::max<int64_t>(10, cs_orders / 1000);
    rows[T_S_CATALOG_ORDER_LINEITEM] = rows[T_S_CATALOG_ORDER] * 10;
    rows[T_S_WEB_ORDER] = std::max<int64_t>(10, ws_orders / 1000);
    rows[T_S_WEB_ORDER_LINEITEM] = rows[T_S_WEB_ORDER] * 12;
    rows[T_S_STORE_RETURNS] = std::max<int64_t>(10, rows[T_STORE_RETURNS] / 1000);
    rows[T_S_CATALOG_RETURNS] = std::max<int64_t>(10, rows[T_CATALOG_RETURNS] / 1000);
    rows[T_S_WEB_RETURNS] = std::max<int64_t>(10, rows[T_WEB_RETURNS] / 1000);
    rows[T_S_INVENTORY] = warehouses * std::max<int64_t>(100, items / 100);
    rows[T_DELETE] = 1;
    rows[T_INVENTORY_DELETE] = 1;
  }
};

static const Scaling* S;  // set in main before any emitter runs

// ---------------------------------------------------------------------------
// Shared field helpers (address block, money chain)
// ---------------------------------------------------------------------------

static void emit_address(Row& w, uint64_t t, uint64_t r, uint64_t c0) {
  w.i(uni(t, r, c0 + 0, 1, 1000));                                   // street number
  w.s(std::string(PK(kStreetNames, t, r, c0 + 1)) + " " +
      PK(kStreetNames, t, r, c0 + 5));                               // street name
  w.s(PK(kStreetTypes, t, r, c0 + 2));                               // street type
  char suite[16];
  if (h4(t, r, c0 + 3) & 1)
    snprintf(suite, sizeof suite, "Suite %d", (int)uni(t, r, c0 + 3, 0, 99));
  else
    snprintf(suite, sizeof suite, "Suite %c", (char)('A' + uni(t, r, c0 + 3, 0, 25)));
  w.s(suite);
  w.s(pick(kCities, geo_active(S->sf, kCities_n), t, r, c0 + 4));    // city
  w.s(pick(kCounties, geo_active(S->sf, kCounties_n), t, r, c0 + 6)); // county
  const char* st = pick(kStates, states_active(S->sf), t, r, c0 + 7);
  w.s(st);                                                           // state
  char zip[8];
  snprintf(zip, sizeof zip, "%05d", (int)uni(t, r, c0 + 8, 10000, 99999));
  w.s(zip);                                                          // zip
  w.s(kCountries[0]);                                                // country
  w.dec(-500 - 100 * uni(t, r, c0 + 9, 0, 3));                       // gmt offset -5..-8
}

// per-line pricing chain shared by the three sales channels; all decimal(7,2)
// math in integer cents.  Returns via out params so returns tables can
// re-derive the sale's economics.
struct Money {
  int64_t qty, wholesale, list, sales, ext_discount, ext_sales, ext_wholesale,
      ext_list, ext_tax, coupon, net_paid, net_paid_tax, net_profit, ship, ext_ship,
      net_paid_ship, net_paid_ship_tax;
};

static void money_chain(uint64_t t, uint64_t r, Money* m) {
  const uint64_t c = 900;  // column namespace for money fields
  m->qty = uni(t, r, c + 0, 1, 100);
  m->wholesale = uni(t, r, c + 1, 100, 10000);            // 1.00 .. 100.00
  int64_t markup = uni(t, r, c + 2, 20, 140);             // 20%..140%
  m->list = m->wholesale * (100 + markup) / 100;
  int64_t discount = uni(t, r, c + 3, 0, 100);            // % off list
  m->sales = m->list * (100 - discount) / 100;
  m->ext_discount = (m->list - m->sales) * m->qty;
  m->ext_sales = m->sales * m->qty;
  m->ext_wholesale = m->wholesale * m->qty;
  m->ext_list = m->list * m->qty;
  int64_t tax_pct = uni(t, r, c + 4, 0, 9);
  m->coupon = (h4(t, r, c + 5) % 100 < 15) ? m->ext_sales * (int64_t)(h4(t, r, c + 6) % 50) / 100 : 0;
  m->net_paid = m->ext_sales - m->coupon;
  m->ext_tax = m->net_paid * tax_pct / 100;
  m->net_paid_tax = m->net_paid + m->ext_tax;
  m->ship = uni(t, r, c + 7, 0, 5000);
  m->ext_ship = m->ship * m->qty / 10;
  m->net_paid_ship = m->net_paid + m->ext_ship;
  m->net_paid_ship_tax = m->net_paid_tax + m->ext_ship;
  m->net_profit = m->net_paid - m->ext_wholesale;
}

// ---------------------------------------------------------------------------
// Dimension emitters: one function per table, row index -> one output line
// ---------------------------------------------------------------------------

static void e_customer_address(Row& w, int64_t r) {
  const uint64_t t = T_CUSTOMER_ADDRESS;
  w.i(r + 1);
  w.s(id16(r + 1));
  emit_address(w, t, r, 10);
  w.s(PK(kLocTypes, t, r, 30), isnull(t, r, 30, 2));
}

static void e_customer_demographics(Row& w, int64_t r) {
  // full enumeration: 2*5*7*20*4*7*7*7 = 1,920,800 combinations
  w.i(r + 1);
  w.s((r % 2) ? "F" : "M");
  w.s(kMarital[(r / 2) % 5]);
  w.s(kEducation[(r / 10) % 7]);
  w.i(500 + 500 * ((r / 70) % 20));
  w.s(kCredit[(r / 1400) % 4]);
  w.i((r / 5600) % 7);
  w.i((r / 39200) % 7);
  w.i((r / 274400) % 7);
}

static void e_date_dim(Row& w, int64_t r) {
  int64_t jday = kDateSkLo + r;
  int y, m, d;
  jday_to_civil(jday, &y, &m, &d);
  int dow = dow_of_jday(jday);
  w.i(jday);
  w.s(id16(jday));
  w.date(jday);
  w.i((y - 1900) * 12 + (m - 1));                 // month_seq
  w.i((jday - kDateSkLo + 1) / 7);                // week_seq
  w.i((y - 1900) * 4 + (m - 1) / 3);              // quarter_seq
  w.i(y);
  w.i(dow);
  w.i(m);
  w.i(d);
  w.i((m - 1) / 3 + 1);                           // qoy
  w.i(y);                                         // fy_year
  w.i((y - 1900) * 4 + (m - 1) / 3);              // fy_quarter_seq
  w.i((jday - kDateSkLo + 1) / 7);                // fy_week_seq
  w.s(kDayNames[dow]);
  char qn[16];
  snprintf(qn, sizeof qn, "%04dQ%d", y, (m - 1) / 3 + 1);
  w.s(qn);                                        // quarter_name
  bool holiday = (m == 12 && d == 25) || (m == 1 && d == 1) || (m == 7 && d == 4) ||
                 (m == 11 && d >= 22 && d <= 28 && dow == 4);
  w.s(holiday ? "Y" : "N");
  w.s((dow == 0 || dow == 6) ? "Y" : "N");        // weekend
  bool follows = false;
  {
    int py, pm, pd;
    jday_to_civil(jday - 1, &py, &pm, &pd);
    int pdow = dow_of_jday(jday - 1);
    follows = (pm == 12 && pd == 25) || (pm == 1 && pd == 1) || (pm == 7 && pd == 4) ||
              (pm == 11 && pd >= 22 && pd <= 28 && pdow == 4);
  }
  w.s(follows ? "Y" : "N");
  w.i(civil_to_jday(y, m, 1));                    // first_dom
  int ny = (m == 12) ? y + 1 : y, nm = (m == 12) ? 1 : m + 1;
  w.i(civil_to_jday(ny, nm, 1) - 1);              // last_dom
  w.i(jday - 365);                                // same_day_ly
  w.i(jday - 91);                                 // same_day_lq
  w.s("N"); w.s("N"); w.s("N"); w.s("N"); w.s("N");
}

static void e_warehouse(Row& w, int64_t r) {
  const uint64_t t = T_WAREHOUSE;
  w.i(r + 1);
  w.s(id16(r + 1));
  w.s(sentence(t, r, 2, 3), isnull(t, r, 2, 2));  // name
  w.i(uni(t, r, 3, 50000, 1000000));              // sq ft
  emit_address(w, t, r, 10);
}

static void e_ship_mode(Row& w, int64_t r) {
  w.i(r + 1);
  w.s(id16(r + 1));
  w.s(kShipTypes[r % 5]);
  w.s(kShipCodes[(r / 5) % 4]);
  w.s(kCarriers[r % kCarriers_n]);
  char contract[32];
  snprintf(contract, sizeof contract, "%c%" PRId64, (char)('A' + r % 26), r * 7 + 13);
  w.s(contract);
}

static void e_time_dim(Row& w, int64_t r) {
  int hour = (int)(r / 3600), minute = (int)((r / 60) % 60), second = (int)(r % 60);
  w.i(r);                                         // t_time_sk is 0-based
  w.s(id16(r + 1));
  w.i(r);
  w.i(hour);
  w.i(minute);
  w.i(second);
  w.s(hour < 12 ? "AM" : "PM");
  w.s(kShifts[hour / 8]);
  w.s(kSubShifts[hour / 6]);
  if (hour >= 6 && hour <= 8) w.s("breakfast");
  else if (hour >= 11 && hour <= 13) w.s("lunch");
  else if (hour >= 17 && hour <= 19) w.s("dinner");
  else w.nul();
}

static void e_reason(Row& w, int64_t r) {
  w.i(r + 1);
  w.s(id16(r + 1));
  w.s(kReasons[r % kReasons_n]);
}

static void e_income_band(Row& w, int64_t r) {
  w.i(r + 1);
  w.i(r * 10000 + (r ? 1 : 0));
  w.i((r + 1) * 10000);
}

static void e_item(Row& w, int64_t r) {
  const uint64_t t = T_ITEM;
  int64_t sk = r + 1;
  w.i(sk);
  w.s(id16(r / 2 + 1));                           // SCD: sk pairs share item_id
  // rec_start/rec_end: even row current (open end), odd row historical
  if (r % 2 == 0) { w.date(civil_to_jday(1997, 10, 27)); w.nul(); }
  else { w.date(civil_to_jday(1993, 10, 27)); w.date(civil_to_jday(1997, 10, 26)); }
  w.s(sentence(t, r, 4, 12), isnull(t, r, 4, 1));  // desc
  int64_t wholesale = uni(t, r, 6, 9, 8800);
  int64_t price = wholesale * (100 + uni(t, r, 5, 10, 120)) / 100;
  w.dec(price, isnull(t, r, 5, 1));               // current_price
  w.dec(wholesale, isnull(t, r, 6, 1));
  int64_t manufact = uni(t, r, 13, 1, 1000);
  int64_t cat = h4(t, r, 12) % kCategories_n;
  int64_t cls = h4(t, r, 10) % kClasses_n;
  int64_t brand = uni(t, r, 8, 1, 10);
  w.i(brand * 1000000 + manufact, isnull(t, r, 8, 1));  // brand_id
  char bbuf[64];
  snprintf(bbuf, sizeof bbuf, "%s%s #%d", kWords[manufact % kWords_n],
           kWords[(manufact / 7) % kWords_n], (int)brand);
  w.s(bbuf, isnull(t, r, 9, 1));                  // brand
  w.i(cls + 1, isnull(t, r, 10, 1));              // class_id
  w.s(kClasses[cls], isnull(t, r, 11, 1));
  w.i(cat + 1, isnull(t, r, 12, 1));              // category_id
  w.s(kCategories[cat], isnull(t, r, 12, 1));
  w.i(manufact, isnull(t, r, 13, 1));
  char mbuf[64];
  snprintf(mbuf, sizeof mbuf, "%s%s", kWords[manufact % kWords_n],
           kWords[(manufact * 3 + 1) % kWords_n]);
  w.s(mbuf, isnull(t, r, 14, 1));                 // manufact
  w.s(PK(kSizes, t, r, 15), isnull(t, r, 15, 1));
  char fbuf[32];
  snprintf(fbuf, sizeof fbuf, "%05dst%d", (int)uni(t, r, 16, 0, 99999), (int)(r % 10));
  w.s(fbuf, isnull(t, r, 16, 1));                 // formulation
  {
    std::string color = PK(kColors, t, r, 17);
    w.s(color, isnull(t, r, 17, 1));
  }
  w.s(PK(kUnits, t, r, 18), isnull(t, r, 18, 1));
  w.s("Unknown", isnull(t, r, 19, 1));            // container
  w.i(uni(t, r, 20, 1, 100), isnull(t, r, 20, 1));  // manager_id
  char pbuf[64];
  snprintf(pbuf, sizeof pbuf, "%s%s%s", kWords[r % kWords_n],
           kWords[(r / 3 + 5) % kWords_n], kWords[(r / 7 + 11) % kWords_n]);
  w.s(pbuf, isnull(t, r, 21, 1));                 // product_name
}

static void e_store(Row& w, int64_t r) {
  const uint64_t t = T_STORE;
  w.i(r + 1);
  w.s(id16(r / 2 + 1));                           // SCD pairs
  if (r % 2 == 0) { w.date(civil_to_jday(1997, 3, 13)); w.nul(); }
  else { w.date(civil_to_jday(1994, 3, 13)); w.date(civil_to_jday(1997, 3, 12)); }
  w.i_or_null(uni(t, r, 4, kDateSkLo, kSalesDateLo), !(h4(t, r, 4) % 10 == 0));  // closed: mostly null
  w.s(kPromoNames[r % kPromoNames_n]);            // store name
  w.i(uni(t, r, 6, 200, 300), isnull(t, r, 6, 1));
  w.i(uni(t, r, 7, 5000000, 10000000), isnull(t, r, 7, 1));
  w.s(kHours[r % 3], isnull(t, r, 8, 1));
  w.s(std::string(PK(kFirstNames, t, r, 9)) + " " + PK(kLastNames, t, r, 9), isnull(t, r, 9, 1));
  w.i(uni(t, r, 10, 1, 10), isnull(t, r, 10, 1)); // market_id
  w.s("Unknown", isnull(t, r, 11, 1));            // geography_class
  w.s(sentence(t, r, 12, 14), isnull(t, r, 12, 1));
  w.s(std::string(PK(kFirstNames, t, r, 13)) + " " + PK(kLastNames, t, r, 13), isnull(t, r, 13, 1));
  w.i(uni(t, r, 14, 1, 6), isnull(t, r, 14, 1));  // division_id
  w.s(kDivNames[h4(t, r, 15) % kDivNames_n], isnull(t, r, 15, 1));
  w.i(uni(t, r, 16, 1, 6), isnull(t, r, 16, 1));  // company_id
  w.s("Unknown", isnull(t, r, 17, 1));
  emit_address(w, t, r, 20);
  // emit_address writes gmt_offset as its last field; store needs tax on top
  w.dec(uni(t, r, 31, 0, 11));                    // s_tax_precentage
}

static void e_call_center(Row& w, int64_t r) {
  const uint64_t t = T_CALL_CENTER;
  w.i(r + 1);
  w.s(id16(r / 2 + 1));
  if (r % 2 == 0) { w.date(civil_to_jday(1998, 1, 1)); w.nul(); }
  else { w.date(civil_to_jday(1996, 1, 1)); w.date(civil_to_jday(1997, 12, 31)); }
  w.i_or_null(0, true);                           // closed_date_sk: always null
  w.i(uni(t, r, 5, kDateSkLo, kSalesDateLo));     // open_date_sk
  char nbuf[32];
  snprintf(nbuf, sizeof nbuf, "%s_%d", kWords[r % kWords_n], (int)(r / 2));
  w.s(nbuf);                                      // cc_name
  w.s(kSizes[r % 3]);                             // class: small/medium/large
  w.i(uni(t, r, 8, 50, 7000));                    // employees
  w.i(uni(t, r, 9, 1000000, 4000000));            // sq_ft
  w.s(kHours[r % 3]);
  w.s(std::string(PK(kFirstNames, t, r, 11)) + " " + PK(kLastNames, t, r, 11));
  w.i(uni(t, r, 12, 1, 6));                       // mkt_id
  w.s(PK(kMktClasses, t, r, 13));
  w.s(sentence(t, r, 14, 14));
  w.s(std::string(PK(kFirstNames, t, r, 15)) + " " + PK(kLastNames, t, r, 15));
  w.i(uni(t, r, 16, 1, 6));                       // division
  w.s(kDivNames[h4(t, r, 17) % kDivNames_n]);
  w.i(uni(t, r, 18, 1, 6));                       // company
  w.s(kDivNames[h4(t, r, 19) % kDivNames_n]);
  emit_address(w, t, r, 20);
  w.dec(uni(t, r, 31, 0, 11));                    // tax_percentage
}

static void e_customer(Row& w, int64_t r) {
  const uint64_t t = T_CUSTOMER;
  w.i(r + 1);
  w.s(id16(r + 1));
  w.i_or_null(uni(t, r, 2, 1, 1920800), isnull(t, r, 2, 2));   // cdemo
  w.i_or_null(uni(t, r, 3, 1, 7200), isnull(t, r, 3, 2));      // hdemo
  w.i_or_null(uni(t, r, 4, 1, S->addresses), isnull(t, r, 4, 2));
  int64_t first_sales = uni(t, r, 6, kSalesDateLo - 2000, kSalesDateHi - 1000);
  w.i_or_null(first_sales + uni(t, r, 5, 0, 30), isnull(t, r, 5, 2));  // first_shipto
  w.i_or_null(first_sales, isnull(t, r, 6, 2));
  bool female = h4(t, r, 100) & 1;
  w.s(female ? PK(kSalutationsF, t, r, 7) : PK(kSalutationsM, t, r, 7), isnull(t, r, 7, 3));
  const char* fn = PK(kFirstNames, t, r, 8);
  const char* ln = PK(kLastNames, t, r, 9);
  w.s(fn, isnull(t, r, 8, 3));
  w.s(ln, isnull(t, r, 9, 3));
  w.s((h4(t, r, 10) & 1) ? "Y" : "N", isnull(t, r, 10, 3));
  w.i(uni(t, r, 11, 1, 28), isnull(t, r, 11, 3)); // birth day
  w.i(uni(t, r, 12, 1, 12), isnull(t, r, 12, 3));
  w.i(uni(t, r, 13, 1924, 1992), isnull(t, r, 13, 3));
  w.s(PK(kBirthCountries, t, r, 14), isnull(t, r, 14, 3));
  w.nul();                                        // c_login (always null in dsdgen)
  char email[96];
  snprintf(email, sizeof email, "%s.%s@%s.edu", fn, ln, kWords[h4(t, r, 16) % kWords_n]);
  w.s(email, isnull(t, r, 16, 3));
  w.i_or_null(uni(t, r, 17, kSalesDateHi - 400, kSalesDateHi), isnull(t, r, 17, 3));
}

static void e_web_site(Row& w, int64_t r) {
  const uint64_t t = T_WEB_SITE;
  w.i(r + 1);
  w.s(id16(r / 2 + 1));
  if (r % 2 == 0) { w.date(civil_to_jday(1997, 8, 16)); w.nul(); }
  else { w.date(civil_to_jday(1995, 8, 16)); w.date(civil_to_jday(1997, 8, 15)); }
  char nbuf[32];
  snprintf(nbuf, sizeof nbuf, "site_%d", (int)(r / 2));
  w.s(nbuf);
  w.i(uni(t, r, 5, kDateSkLo, kSalesDateLo));     // open
  w.i_or_null(uni(t, r, 6, kSalesDateLo, kSalesDateHi), !(h4(t, r, 6) % 10 == 0));
  w.s("Unknown");                                 // class
  w.s(std::string(PK(kFirstNames, t, r, 8)) + " " + PK(kLastNames, t, r, 8));
  w.i(uni(t, r, 9, 1, 6));
  w.s(PK(kMktClasses, t, r, 10));
  w.s(sentence(t, r, 11, 14));
  w.s(std::string(PK(kFirstNames, t, r, 12)) + " " + PK(kLastNames, t, r, 12));
  w.i(uni(t, r, 13, 1, 6));
  w.s(kDivNames[h4(t, r, 14) % kDivNames_n]);
  emit_address(w, t, r, 20);
  w.dec(uni(t, r, 31, 0, 11));                    // tax_percentage
}

static void e_household_demographics(Row& w, int64_t r) {
  // 20 income bands * 6 buy potentials * 10 dep counts * 6 vehicle counts
  w.i(r + 1);
  w.i(r % 20 + 1);
  w.s(kBuyPotential[(r / 20) % 6]);
  w.i((r / 120) % 10);
  w.i((r / 1200) % 6);  // vehicle count 0..5
}

static void e_web_page(Row& w, int64_t r) {
  const uint64_t t = T_WEB_PAGE;
  w.i(r + 1);
  w.s(id16(r / 2 + 1));
  if (r % 2 == 0) { w.date(civil_to_jday(1997, 9, 3)); w.nul(); }
  else { w.date(civil_to_jday(1995, 9, 3)); w.date(civil_to_jday(1997, 9, 2)); }
  w.i(uni(t, r, 4, kSalesDateLo - 1000, kSalesDateLo));  // creation
  w.i(uni(t, r, 5, kSalesDateLo, kSalesDateHi));  // access
  bool autogen = h4(t, r, 6) % 100 < 30;
  w.s(autogen ? "Y" : "N");
  w.i_or_null(uni(t, r, 7, 1, S->customers), !autogen);  // customer_sk when autogen
  char url[40];
  snprintf(url, sizeof url, "http://www.foo.com/page%d.html", (int)r);
  w.s(url, isnull(t, r, 8, 2));
  w.s(PK(kWebTypes, t, r, 9));
  w.i(uni(t, r, 10, 100, 8000));                  // char_count
  w.i(uni(t, r, 11, 2, 25));                      // link_count
  w.i(uni(t, r, 12, 1, 7));                       // image_count
  w.i(uni(t, r, 13, 0, 4));                       // max_ad_count
}

static void e_promotion(Row& w, int64_t r) {
  const uint64_t t = T_PROMOTION;
  w.i(r + 1);
  w.s(id16(r + 1));
  int64_t start = uni(t, r, 2, kSalesDateLo, kSalesDateHi - 60);
  w.i_or_null(start, isnull(t, r, 2, 2));
  w.i_or_null(start + uni(t, r, 3, 10, 60), isnull(t, r, 3, 2));
  w.i_or_null(uni(t, r, 4, 1, S->items), isnull(t, r, 4, 2));
  w.dec(100000, isnull(t, r, 5, 2));              // p_cost = 1000.00
  w.i(1);                                         // response_target
  w.s(kPromoNames[r % kPromoNames_n], isnull(t, r, 7, 2));
  for (int c = 8; c <= 15; c++)                   // 8 channel flags
    w.s((h4(t, r, c) & 1) ? "Y" : "N", isnull(t, r, c, 2));
  w.s(sentence(t, r, 16, 10), isnull(t, r, 16, 2));
  w.s("Unknown", isnull(t, r, 17, 2));            // purpose
  w.s((h4(t, r, 18) & 1) ? "Y" : "N");            // discount_active
}

static void e_catalog_page(Row& w, int64_t r) {
  const uint64_t t = T_CATALOG_PAGE;
  w.i(r + 1);
  w.s(id16(r + 1));
  int64_t start = kSalesDateLo + (r / 108) * 30 % (kSalesDateHi - kSalesDateLo);
  w.i(start);
  w.i(start + 30);
  w.s(kDepartments[0], isnull(t, r, 4, 1));
  w.i(r / 108 + 1);                               // catalog_number
  w.i(r % 108 + 1);                               // catalog_page_number
  w.s(sentence(t, r, 7, 10), isnull(t, r, 7, 1));
  w.s(kCatalogTypes[(r / 108) % 3], isnull(t, r, 8, 1));
}

static void e_inventory(Row& w, int64_t r) {
  const uint64_t t = T_INVENTORY;
  // row -> (week, warehouse, item_id); weekly snapshots across the sales window
  int64_t ids = S->item_ids();
  int64_t per_week = S->warehouses * ids;
  int64_t week = r / per_week;
  int64_t rem = r % per_week;
  int64_t wh = rem / ids;
  int64_t id = rem % ids;
  // spread the (possibly sub-SF1-shrunken) snapshot count across the FULL
  // 5-year window so date-window queries (q21/q22 month ranges) always find
  // snapshots; at full scale n_weeks == 261 and the stride is exactly 7 days
  int64_t n_weeks = std::max<int64_t>(1, S->rows[T_INVENTORY] / per_week);
  w.i(kSalesDateLo + ((week * 261) / n_weeks) * 7 + 3);
  // the item_sk of the id's version valid on the snapshot's date (dsdgen's
  // matchSCDSK): every date here follows the current row's rec_start
  // (1997-10-27), so it is the id's even row, sk 2 * id + 1
  w.i(2 * id + 1);
  w.i(wh + 1);
  // stockout-skewed on-hand quantity: ~40% of snapshots near zero, the rest
  // uniform. A pure uniform gives every (item, warehouse) group a coefficient
  // of variation ~0.58, which degenerates q39's `cov > 1` filter to empty;
  // stockouts push per-group cov across 1 the way real inventories do.
  int64_t q = (h4(t, r, 3) % 10) < 4 ? (int64_t)(h4(t, r, 4) % 5)
                                     : (int64_t)uni(t, r, 5, 0, 1000);
  w.i_or_null(q, isnull(t, r, 3, 2));
}

// ---------------------------------------------------------------------------
// Fact emitters. Line-level facts derive shared fields from the parent
// ticket/order hash stream so multi-line tickets are consistent without
// cross-row state; returns re-derive their originating sale.
// ---------------------------------------------------------------------------

struct SsLine {  // store_sales row r = (ticket = r/12, line = r%12)
  int64_t ticket, line, sold_date, sold_time, item, customer, cdemo, hdemo, addr,
      store, promo;
  Money m;
};

static void derive_ss(int64_t r, SsLine* o) {
  const uint64_t t = T_STORE_SALES;
  o->ticket = r / 12 + 1;
  o->line = r % 12;
  uint64_t tk = (uint64_t)o->ticket;
  o->sold_date = kSalesDateLo + (int64_t)(h4(t, tk, 500) % (uint64_t)(kSalesDateHi - kSalesDateLo + 1));
  o->sold_time = 28800 + (int64_t)(h4(t, tk, 501) % 43200);  // 8:00..20:00
  o->customer = 1 + (int64_t)(h4(t, tk, 502) % (uint64_t)S->customers);
  o->cdemo = 1 + (int64_t)(h4(t, tk, 503) % 1920800ULL);
  o->hdemo = 1 + (int64_t)(h4(t, tk, 504) % 7200ULL);
  o->addr = 1 + (int64_t)(h4(t, tk, 505) % (uint64_t)S->addresses);
  o->store = 1 + (int64_t)(h4(t, tk, 506) % (uint64_t)S->stores);
  o->item = 1 + (int64_t)(h4(t, (uint64_t)r, 507) % (uint64_t)S->items);
  o->promo = 1 + (int64_t)(h4(t, (uint64_t)r, 508) % (uint64_t)S->promotions);
  money_chain(t, (uint64_t)r, &o->m);
}

static void e_store_sales(Row& w, int64_t r) {
  const uint64_t t = T_STORE_SALES;
  SsLine L;
  derive_ss(r, &L);
  w.i_or_null(L.sold_date, isnull(t, r, 0, 4));
  w.i_or_null(L.sold_time, isnull(t, r, 1, 4));
  w.i(L.item);
  w.i_or_null(L.customer, isnull(t, r, 3, 4));
  w.i_or_null(L.cdemo, isnull(t, r, 4, 4));
  w.i_or_null(L.hdemo, isnull(t, r, 5, 4));
  w.i_or_null(L.addr, isnull(t, r, 6, 4));
  w.i_or_null(L.store, isnull(t, r, 7, 4));
  w.i_or_null(L.promo, isnull(t, r, 8, 4));
  w.i(L.ticket);
  w.i_or_null(L.m.qty, isnull(t, r, 10, 4));
  w.dec(L.m.wholesale, isnull(t, r, 11, 4));
  w.dec(L.m.list, isnull(t, r, 12, 4));
  w.dec(L.m.sales, isnull(t, r, 13, 4));
  w.dec(L.m.ext_discount, isnull(t, r, 14, 4));
  w.dec(L.m.ext_sales, isnull(t, r, 15, 4));
  w.dec(L.m.ext_wholesale, isnull(t, r, 16, 4));
  w.dec(L.m.ext_list, isnull(t, r, 17, 4));
  w.dec(L.m.ext_tax, isnull(t, r, 18, 4));
  w.dec(L.m.coupon, isnull(t, r, 19, 4));
  w.dec(L.m.net_paid, isnull(t, r, 20, 4));
  w.dec(L.m.net_paid_tax, isnull(t, r, 21, 4));
  w.dec(L.m.net_profit, isnull(t, r, 22, 4));
}

static void e_store_returns(Row& w, int64_t r) {
  const uint64_t t = T_STORE_RETURNS;
  // return r originates from sale row s (stride 10 with jitter)
  int64_t s = r * 10 + (int64_t)(h4(t, r, 600) % 10);
  if (s >= S->rows[T_STORE_SALES]) s = s % S->rows[T_STORE_SALES];
  SsLine L;
  derive_ss(s, &L);
  int64_t ret_date = L.sold_date + 1 + (int64_t)(h4(t, r, 601) % 120);
  int64_t qty = 1 + (int64_t)(h4(t, r, 602) % (uint64_t)L.m.qty);
  int64_t amt = L.m.sales * qty;
  int64_t tax = amt * 5 / 100;
  int64_t fee = 50 + (int64_t)(h4(t, r, 603) % 10000);
  int64_t ship = 100 + (int64_t)(h4(t, r, 604) % 5000);
  int64_t refunded = amt * (int64_t)(h4(t, r, 605) % 101) / 100;
  int64_t reversed = (amt - refunded) / 2;
  int64_t credit = amt - refunded - reversed;
  w.i_or_null(ret_date, isnull(t, r, 0, 4));
  w.i_or_null(28800 + (int64_t)(h4(t, r, 606) % 43200), isnull(t, r, 1, 4));
  w.i(L.item);
  w.i_or_null(L.customer, isnull(t, r, 3, 4));
  w.i_or_null(L.cdemo, isnull(t, r, 4, 4));
  w.i_or_null(L.hdemo, isnull(t, r, 5, 4));
  w.i_or_null(L.addr, isnull(t, r, 6, 4));
  w.i_or_null(L.store, isnull(t, r, 7, 4));
  w.i_or_null(1 + (int64_t)(h4(t, r, 607) % (uint64_t)S->reasons), isnull(t, r, 8, 4));
  w.i(L.ticket);
  w.i_or_null(qty, isnull(t, r, 10, 4));
  w.dec(amt, isnull(t, r, 11, 4));
  w.dec(tax, isnull(t, r, 12, 4));
  w.dec(amt + tax, isnull(t, r, 13, 4));
  w.dec(fee, isnull(t, r, 14, 4));
  w.dec(ship * qty, isnull(t, r, 15, 4));
  w.dec(refunded, isnull(t, r, 16, 4));
  w.dec(reversed, isnull(t, r, 17, 4));
  w.dec(credit, isnull(t, r, 18, 4));
  w.dec(fee + ship * qty + tax, isnull(t, r, 19, 4));  // net_loss
}

struct CsLine {  // catalog_sales row r = (order = r/10, line = r%10)
  int64_t order, line, sold_date, sold_time, ship_date, bill_customer, bill_cdemo,
      bill_hdemo, bill_addr, ship_customer, ship_cdemo, ship_hdemo, ship_addr,
      call_center, catalog_page, ship_mode, warehouse, item, promo;
  Money m;
};

static void derive_cs(int64_t r, CsLine* o) {
  const uint64_t t = T_CATALOG_SALES;
  o->order = r / 10 + 1;
  o->line = r % 10;
  uint64_t ok = (uint64_t)o->order;
  o->sold_date = kSalesDateLo + (int64_t)(h4(t, ok, 500) % (uint64_t)(kSalesDateHi - kSalesDateLo + 1));
  o->sold_time = (int64_t)(h4(t, ok, 501) % 86400);
  o->ship_date = o->sold_date + 2 + (int64_t)(h4(t, (uint64_t)r, 502) % 60);
  o->bill_customer = 1 + (int64_t)(h4(t, ok, 503) % (uint64_t)S->customers);
  o->bill_cdemo = 1 + (int64_t)(h4(t, ok, 504) % 1920800ULL);
  o->bill_hdemo = 1 + (int64_t)(h4(t, ok, 505) % 7200ULL);
  o->bill_addr = 1 + (int64_t)(h4(t, ok, 506) % (uint64_t)S->addresses);
  if (h4(t, ok, 507) % 100 < 85) {  // ship-to == bill-to 85% of the time
    o->ship_customer = o->bill_customer; o->ship_cdemo = o->bill_cdemo;
    o->ship_hdemo = o->bill_hdemo; o->ship_addr = o->bill_addr;
  } else {
    o->ship_customer = 1 + (int64_t)(h4(t, ok, 508) % (uint64_t)S->customers);
    o->ship_cdemo = 1 + (int64_t)(h4(t, ok, 509) % 1920800ULL);
    o->ship_hdemo = 1 + (int64_t)(h4(t, ok, 510) % 7200ULL);
    o->ship_addr = 1 + (int64_t)(h4(t, ok, 511) % (uint64_t)S->addresses);
  }
  o->call_center = 1 + (int64_t)(h4(t, ok, 512) % (uint64_t)S->call_centers);
  o->catalog_page = 1 + (int64_t)(h4(t, (uint64_t)r, 513) % (uint64_t)S->catalog_pages);
  o->ship_mode = 1 + (int64_t)(h4(t, ok, 514) % 20ULL);
  o->warehouse = 1 + (int64_t)(h4(t, (uint64_t)r, 515) % (uint64_t)S->warehouses);
  o->item = 1 + (int64_t)(h4(t, (uint64_t)r, 516) % (uint64_t)S->items);
  o->promo = 1 + (int64_t)(h4(t, (uint64_t)r, 517) % (uint64_t)S->promotions);
  // Cross-channel repurchase correlation: ~20% of catalog lines are the same
  // customer re-buying the same item after a store return (what q17/q25/q29
  // join for: ss -> sr -> cs on customer+item, catalog purchase after the
  // return). Derived from a store_returns row so the triple exists at every
  // scale.
  if (S->rows[T_STORE_RETURNS] > 0 && h4(t, (uint64_t)r, 518) % 5 == 0) {
    uint64_t j = h4(t, (uint64_t)r, 519) % (uint64_t)S->rows[T_STORE_RETURNS];
    int64_t sr = (int64_t)j * 10 + (int64_t)(h4(T_STORE_RETURNS, j, 600) % 10);
    if (sr >= S->rows[T_STORE_SALES]) sr = sr % S->rows[T_STORE_SALES];
    SsLine L;
    derive_ss(sr, &L);
    int64_t ret_date = L.sold_date + 1 + (int64_t)(h4(T_STORE_RETURNS, j, 601) % 120);
    o->bill_customer = L.customer;
    o->item = L.item;
    o->sold_date = std::min<int64_t>(
        kSalesDateHi, ret_date + (int64_t)(h4(t, (uint64_t)r, 520) % 90));
    // ship follows the overridden sale; never before it
    o->ship_date = o->sold_date + 2 + (int64_t)(h4(t, (uint64_t)r, 502) % 60);
  }
  money_chain(t, (uint64_t)r, &o->m);
}

static void e_catalog_sales(Row& w, int64_t r) {
  const uint64_t t = T_CATALOG_SALES;
  CsLine L;
  derive_cs(r, &L);
  w.i_or_null(L.sold_date, isnull(t, r, 0, 4));
  w.i_or_null(L.sold_time, isnull(t, r, 1, 4));
  w.i_or_null(L.ship_date, isnull(t, r, 2, 4));
  w.i_or_null(L.bill_customer, isnull(t, r, 3, 4));
  w.i_or_null(L.bill_cdemo, isnull(t, r, 4, 4));
  w.i_or_null(L.bill_hdemo, isnull(t, r, 5, 4));
  w.i_or_null(L.bill_addr, isnull(t, r, 6, 4));
  w.i_or_null(L.ship_customer, isnull(t, r, 7, 4));
  w.i_or_null(L.ship_cdemo, isnull(t, r, 8, 4));
  w.i_or_null(L.ship_hdemo, isnull(t, r, 9, 4));
  w.i_or_null(L.ship_addr, isnull(t, r, 10, 4));
  w.i_or_null(L.call_center, isnull(t, r, 11, 4));
  w.i_or_null(L.catalog_page, isnull(t, r, 12, 4));
  w.i_or_null(L.ship_mode, isnull(t, r, 13, 4));
  w.i_or_null(L.warehouse, isnull(t, r, 14, 4));
  w.i(L.item);
  w.i_or_null(L.promo, isnull(t, r, 16, 4));
  w.i(L.order);
  w.i_or_null(L.m.qty, isnull(t, r, 18, 4));
  w.dec(L.m.wholesale, isnull(t, r, 19, 4));
  w.dec(L.m.list, isnull(t, r, 20, 4));
  w.dec(L.m.sales, isnull(t, r, 21, 4));
  w.dec(L.m.ext_discount, isnull(t, r, 22, 4));
  w.dec(L.m.ext_sales, isnull(t, r, 23, 4));
  w.dec(L.m.ext_wholesale, isnull(t, r, 24, 4));
  w.dec(L.m.ext_list, isnull(t, r, 25, 4));
  w.dec(L.m.ext_tax, isnull(t, r, 26, 4));
  w.dec(L.m.coupon, isnull(t, r, 27, 4));
  w.dec(L.m.ext_ship, isnull(t, r, 28, 4));
  w.dec(L.m.net_paid, isnull(t, r, 29, 4));
  w.dec(L.m.net_paid_tax, isnull(t, r, 30, 4));
  w.dec(L.m.net_paid_ship, isnull(t, r, 31, 4));
  w.dec(L.m.net_paid_ship_tax, isnull(t, r, 32, 4));
  w.dec(L.m.net_profit, isnull(t, r, 33, 4));
}

static void e_catalog_returns(Row& w, int64_t r) {
  const uint64_t t = T_CATALOG_RETURNS;
  int64_t s = r * 10 + (int64_t)(h4(t, r, 600) % 10);
  if (s >= S->rows[T_CATALOG_SALES]) s = s % S->rows[T_CATALOG_SALES];
  CsLine L;
  derive_cs(s, &L);
  int64_t ret_date = L.ship_date + 1 + (int64_t)(h4(t, r, 601) % 120);
  int64_t qty = 1 + (int64_t)(h4(t, r, 602) % (uint64_t)L.m.qty);
  int64_t amt = L.m.sales * qty;
  int64_t tax = amt * 5 / 100;
  int64_t fee = 50 + (int64_t)(h4(t, r, 603) % 10000);
  int64_t ship = 100 + (int64_t)(h4(t, r, 604) % 5000);
  int64_t refunded = amt * (int64_t)(h4(t, r, 605) % 101) / 100;
  int64_t reversed = (amt - refunded) / 2;
  int64_t credit = amt - refunded - reversed;
  w.i_or_null(ret_date, isnull(t, r, 0, 4));
  w.i_or_null((int64_t)(h4(t, r, 606) % 86400), isnull(t, r, 1, 4));
  w.i(L.item);
  w.i_or_null(L.bill_customer, isnull(t, r, 3, 4));
  w.i_or_null(L.bill_cdemo, isnull(t, r, 4, 4));
  w.i_or_null(L.bill_hdemo, isnull(t, r, 5, 4));
  w.i_or_null(L.bill_addr, isnull(t, r, 6, 4));
  w.i_or_null(L.ship_customer, isnull(t, r, 7, 4));
  w.i_or_null(L.ship_cdemo, isnull(t, r, 8, 4));
  w.i_or_null(L.ship_hdemo, isnull(t, r, 9, 4));
  w.i_or_null(L.ship_addr, isnull(t, r, 10, 4));
  w.i_or_null(L.call_center, isnull(t, r, 11, 4));
  w.i_or_null(L.catalog_page, isnull(t, r, 12, 4));
  w.i_or_null(L.ship_mode, isnull(t, r, 13, 4));
  w.i_or_null(L.warehouse, isnull(t, r, 14, 4));
  w.i_or_null(1 + (int64_t)(h4(t, r, 607) % (uint64_t)S->reasons), isnull(t, r, 15, 4));
  w.i(L.order);
  w.i_or_null(qty, isnull(t, r, 17, 4));
  w.dec(amt, isnull(t, r, 18, 4));
  w.dec(tax, isnull(t, r, 19, 4));
  w.dec(amt + tax, isnull(t, r, 20, 4));
  w.dec(fee, isnull(t, r, 21, 4));
  w.dec(ship * qty, isnull(t, r, 22, 4));
  w.dec(refunded, isnull(t, r, 23, 4));
  w.dec(reversed, isnull(t, r, 24, 4));
  w.dec(credit, isnull(t, r, 25, 4));
  w.dec(fee + ship * qty + tax, isnull(t, r, 26, 4));
}

struct WsLine {  // web_sales row r = (order = r/12, line = r%12)
  int64_t order, line, sold_date, sold_time, ship_date, bill_customer, bill_cdemo,
      bill_hdemo, bill_addr, ship_customer, ship_cdemo, ship_hdemo, ship_addr,
      web_page, web_site, ship_mode, warehouse, item, promo;
  Money m;
};

static void derive_ws(int64_t r, WsLine* o) {
  const uint64_t t = T_WEB_SALES;
  o->order = r / 12 + 1;
  o->line = r % 12;
  uint64_t ok = (uint64_t)o->order;
  o->sold_date = kSalesDateLo + (int64_t)(h4(t, ok, 500) % (uint64_t)(kSalesDateHi - kSalesDateLo + 1));
  o->sold_time = (int64_t)(h4(t, ok, 501) % 86400);
  o->ship_date = o->sold_date + 2 + (int64_t)(h4(t, (uint64_t)r, 502) % 60);
  o->bill_customer = 1 + (int64_t)(h4(t, ok, 503) % (uint64_t)S->customers);
  o->bill_cdemo = 1 + (int64_t)(h4(t, ok, 504) % 1920800ULL);
  o->bill_hdemo = 1 + (int64_t)(h4(t, ok, 505) % 7200ULL);
  o->bill_addr = 1 + (int64_t)(h4(t, ok, 506) % (uint64_t)S->addresses);
  if (h4(t, ok, 507) % 100 < 90) {
    o->ship_customer = o->bill_customer; o->ship_cdemo = o->bill_cdemo;
    o->ship_hdemo = o->bill_hdemo; o->ship_addr = o->bill_addr;
  } else {
    o->ship_customer = 1 + (int64_t)(h4(t, ok, 508) % (uint64_t)S->customers);
    o->ship_cdemo = 1 + (int64_t)(h4(t, ok, 509) % 1920800ULL);
    o->ship_hdemo = 1 + (int64_t)(h4(t, ok, 510) % 7200ULL);
    o->ship_addr = 1 + (int64_t)(h4(t, ok, 511) % (uint64_t)S->addresses);
  }
  o->web_page = 1 + (int64_t)(h4(t, ok, 512) % (uint64_t)S->web_pages);
  o->web_site = 1 + (int64_t)(h4(t, ok, 513) % (uint64_t)S->web_sites);
  o->ship_mode = 1 + (int64_t)(h4(t, ok, 514) % 20ULL);
  o->warehouse = 1 + (int64_t)(h4(t, (uint64_t)r, 515) % (uint64_t)S->warehouses);
  o->item = 1 + (int64_t)(h4(t, (uint64_t)r, 516) % (uint64_t)S->items);
  o->promo = 1 + (int64_t)(h4(t, (uint64_t)r, 517) % (uint64_t)S->promotions);
  money_chain(t, (uint64_t)r, &o->m);
}

static void e_web_sales(Row& w, int64_t r) {
  const uint64_t t = T_WEB_SALES;
  WsLine L;
  derive_ws(r, &L);
  w.i_or_null(L.sold_date, isnull(t, r, 0, 4));
  w.i_or_null(L.sold_time, isnull(t, r, 1, 4));
  w.i_or_null(L.ship_date, isnull(t, r, 2, 4));
  w.i(L.item);
  w.i_or_null(L.bill_customer, isnull(t, r, 4, 4));
  w.i_or_null(L.bill_cdemo, isnull(t, r, 5, 4));
  w.i_or_null(L.bill_hdemo, isnull(t, r, 6, 4));
  w.i_or_null(L.bill_addr, isnull(t, r, 7, 4));
  w.i_or_null(L.ship_customer, isnull(t, r, 8, 4));
  w.i_or_null(L.ship_cdemo, isnull(t, r, 9, 4));
  w.i_or_null(L.ship_hdemo, isnull(t, r, 10, 4));
  w.i_or_null(L.ship_addr, isnull(t, r, 11, 4));
  w.i_or_null(L.web_page, isnull(t, r, 12, 4));
  w.i_or_null(L.web_site, isnull(t, r, 13, 4));
  w.i_or_null(L.ship_mode, isnull(t, r, 14, 4));
  w.i_or_null(L.warehouse, isnull(t, r, 15, 4));
  w.i_or_null(L.promo, isnull(t, r, 16, 4));
  w.i(L.order);
  w.i_or_null(L.m.qty, isnull(t, r, 18, 4));
  w.dec(L.m.wholesale, isnull(t, r, 19, 4));
  w.dec(L.m.list, isnull(t, r, 20, 4));
  w.dec(L.m.sales, isnull(t, r, 21, 4));
  w.dec(L.m.ext_discount, isnull(t, r, 22, 4));
  w.dec(L.m.ext_sales, isnull(t, r, 23, 4));
  w.dec(L.m.ext_wholesale, isnull(t, r, 24, 4));
  w.dec(L.m.ext_list, isnull(t, r, 25, 4));
  w.dec(L.m.ext_tax, isnull(t, r, 26, 4));
  w.dec(L.m.coupon, isnull(t, r, 27, 4));
  w.dec(L.m.ext_ship, isnull(t, r, 28, 4));
  w.dec(L.m.net_paid, isnull(t, r, 29, 4));
  w.dec(L.m.net_paid_tax, isnull(t, r, 30, 4));
  w.dec(L.m.net_paid_ship, isnull(t, r, 31, 4));
  w.dec(L.m.net_paid_ship_tax, isnull(t, r, 32, 4));
  w.dec(L.m.net_profit, isnull(t, r, 33, 4));
}

static void e_web_returns(Row& w, int64_t r) {
  const uint64_t t = T_WEB_RETURNS;
  int64_t s = r * 10 + (int64_t)(h4(t, r, 600) % 10);
  if (s >= S->rows[T_WEB_SALES]) s = s % S->rows[T_WEB_SALES];
  WsLine L;
  derive_ws(s, &L);
  int64_t ret_date = L.ship_date + 1 + (int64_t)(h4(t, r, 601) % 120);
  int64_t qty = 1 + (int64_t)(h4(t, r, 602) % (uint64_t)L.m.qty);
  int64_t amt = L.m.sales * qty;
  int64_t tax = amt * 5 / 100;
  int64_t fee = 50 + (int64_t)(h4(t, r, 603) % 10000);
  int64_t ship = 100 + (int64_t)(h4(t, r, 604) % 5000);
  int64_t refunded = amt * (int64_t)(h4(t, r, 605) % 101) / 100;
  int64_t reversed = (amt - refunded) / 2;
  int64_t credit = amt - refunded - reversed;
  w.i_or_null(ret_date, isnull(t, r, 0, 4));
  w.i_or_null((int64_t)(h4(t, r, 606) % 86400), isnull(t, r, 1, 4));
  w.i(L.item);
  w.i_or_null(L.bill_customer, isnull(t, r, 3, 4));
  w.i_or_null(L.bill_cdemo, isnull(t, r, 4, 4));
  w.i_or_null(L.bill_hdemo, isnull(t, r, 5, 4));
  w.i_or_null(L.bill_addr, isnull(t, r, 6, 4));
  w.i_or_null(L.ship_customer, isnull(t, r, 7, 4));
  w.i_or_null(L.ship_cdemo, isnull(t, r, 8, 4));
  w.i_or_null(L.ship_hdemo, isnull(t, r, 9, 4));
  w.i_or_null(L.ship_addr, isnull(t, r, 10, 4));
  w.i_or_null(L.web_page, isnull(t, r, 11, 4));
  w.i_or_null(1 + (int64_t)(h4(t, r, 607) % (uint64_t)S->reasons), isnull(t, r, 12, 4));
  w.i(L.order);
  w.i_or_null(qty, isnull(t, r, 14, 4));
  w.dec(amt, isnull(t, r, 15, 4));
  w.dec(tax, isnull(t, r, 16, 4));
  w.dec(amt + tax, isnull(t, r, 17, 4));
  w.dec(fee, isnull(t, r, 18, 4));
  w.dec(ship * qty, isnull(t, r, 19, 4));
  w.dec(refunded, isnull(t, r, 20, 4));
  w.dec(reversed, isnull(t, r, 21, 4));
  w.dec(credit, isnull(t, r, 22, 4));
  w.dec(fee + ship * qty + tax, isnull(t, r, 23, 4));
}

// ---------------------------------------------------------------------------
// Refresh (-update) emitters: the s_* source tables Data Maintenance joins
// against (ref: nds/data_maintenance/LF_*.sql), plus the delete-date files.
// ---------------------------------------------------------------------------

static int g_update = 0;  // current -update number (0 = base generation)

static inline int64_t upd_window_lo() { return kSalesDateLo + (int64_t)(g_update - 1) * 14; }
static inline int64_t upd_window_hi() { return upd_window_lo() + 13; }

static inline int64_t upd_date(uint64_t t, int64_t r, uint64_t c) {
  return upd_window_lo() + (int64_t)(h4(t, (uint64_t)r, c) % 14);
}

static std::string time_str(int64_t secs) {
  char buf[12];
  snprintf(buf, sizeof buf, "%02d:%02d:%02d", (int)(secs / 3600), (int)((secs / 60) % 60),
           (int)(secs % 60));
  return std::string(buf);
}

// business-key helpers honouring the SCD pairing of dims (valid ids are
// id16(1 .. n/2) for item/store/call_center/web_site/web_page)
static std::string rk_item(uint64_t t, int64_t r, uint64_t c) {
  return id16(1 + (int64_t)(h4(t, (uint64_t)r, c) % (uint64_t)std::max<int64_t>(1, S->items / 2)));
}
static std::string rk_cust(uint64_t t, int64_t r, uint64_t c) {
  return id16(1 + (int64_t)(h4(t, (uint64_t)r, c) % (uint64_t)S->customers));
}

static void e_s_purchase(Row& w, int64_t r) {
  const uint64_t t = T_S_PURCHASE;
  w.i(g_update * 10000000LL + r + 1);
  w.s(id16(1 + (int64_t)(h4(t, r, 1) % (uint64_t)std::max<int64_t>(1, S->stores / 2))));
  w.s(rk_cust(t, r, 2));
  w.s(date_str(upd_date(t, r, 3)));
  w.i(28800 + (int64_t)(h4(t, r, 4) % 43200));
  w.i(uni(t, r, 5, 1, 1000));   // register
  w.i(uni(t, r, 6, 1, 1000));   // clerk
  w.s(sentence(t, r, 7, 8));
}

static void e_s_purchase_lineitem(Row& w, int64_t r) {
  const uint64_t t = T_S_PURCHASE_LINEITEM;
  w.i(g_update * 10000000LL + r / 12 + 1);
  w.i(r % 12 + 1);
  w.s(rk_item(t, r, 2));
  w.s(id16(1 + (int64_t)(h4(t, r, 3) % (uint64_t)S->promotions)));
  w.i(uni(t, r, 4, 1, 100));
  w.dec(uni(t, r, 5, 100, 30000));
  w.dec((h4(t, r, 6) % 100 < 15) ? uni(t, r, 7, 0, 5000) : 0);
  w.s(sentence(t, r, 8, 8));
}

static void e_s_catalog_order(Row& w, int64_t r) {
  const uint64_t t = T_S_CATALOG_ORDER;
  w.i(g_update * 10000000LL + r + 1);
  w.s(rk_cust(t, r, 1));
  w.s(rk_cust(t, r, 2));
  w.s(date_str(upd_date(t, r, 3)));
  w.i((int64_t)(h4(t, r, 4) % 86400));
  w.s(id16(1 + (int64_t)(h4(t, r, 5) % 20)));
  w.s(id16(1 + (int64_t)(h4(t, r, 6) % (uint64_t)std::max<int64_t>(1, S->call_centers / 2))));
  w.s(sentence(t, r, 7, 8));
}

static void e_s_catalog_order_lineitem(Row& w, int64_t r) {
  const uint64_t t = T_S_CATALOG_ORDER_LINEITEM;
  w.i(g_update * 10000000LL + r / 10 + 1);
  w.i(r % 10 + 1);
  w.s(rk_item(t, r, 2));
  w.s(id16(1 + (int64_t)(h4(t, r, 3) % (uint64_t)S->promotions)));
  w.i(uni(t, r, 4, 1, 100));
  w.dec(uni(t, r, 5, 100, 30000));
  w.dec((h4(t, r, 6) % 100 < 15) ? uni(t, r, 7, 0, 5000) : 0);
  w.s(id16(1 + (int64_t)(h4(t, r, 8) % (uint64_t)S->warehouses)));
  w.s(date_str(upd_date(t, r, 9) + 2 + (int64_t)(h4(t, r, 10) % 30)));
  w.i(uni(t, r, 11, 1, S->catalog_pages / 108 + 1));
  w.i(uni(t, r, 12, 1, 108));
  w.dec(uni(t, r, 13, 0, 5000));
}

static void e_s_web_order(Row& w, int64_t r) {
  const uint64_t t = T_S_WEB_ORDER;
  w.i(g_update * 10000000LL + r + 1);
  w.s(rk_cust(t, r, 1));
  w.s(rk_cust(t, r, 2));
  w.s(date_str(upd_date(t, r, 3)));
  w.i((int64_t)(h4(t, r, 4) % 86400));
  w.s(id16(1 + (int64_t)(h4(t, r, 5) % 20)));
  w.s(id16(1 + (int64_t)(h4(t, r, 6) % (uint64_t)std::max<int64_t>(1, S->web_sites / 2))));
  w.s(sentence(t, r, 7, 8));
}

static void e_s_web_order_lineitem(Row& w, int64_t r) {
  const uint64_t t = T_S_WEB_ORDER_LINEITEM;
  w.i(g_update * 10000000LL + r / 12 + 1);
  w.i(r % 12 + 1);
  w.s(rk_item(t, r, 2));
  w.s(id16(1 + (int64_t)(h4(t, r, 3) % (uint64_t)S->promotions)));
  w.i(uni(t, r, 4, 1, 100));
  w.dec(uni(t, r, 5, 100, 30000));
  w.dec((h4(t, r, 6) % 100 < 15) ? uni(t, r, 7, 0, 5000) : 0);
  w.s(id16(1 + (int64_t)(h4(t, r, 8) % (uint64_t)S->warehouses)));
  w.s(date_str(upd_date(t, r, 9) + 2 + (int64_t)(h4(t, r, 10) % 30)));
  w.dec(uni(t, r, 11, 0, 5000));
  w.s(id16(1 + (int64_t)(h4(t, r, 12) % (uint64_t)std::max<int64_t>(1, S->web_pages / 2))));
}

static void e_s_store_returns(Row& w, int64_t r) {
  const uint64_t t = T_S_STORE_RETURNS;
  int64_t qty = uni(t, r, 100, 1, 50);
  int64_t amt = uni(t, r, 101, 100, 20000) * qty;
  int64_t refunded = amt * (int64_t)(h4(t, r, 102) % 101) / 100;
  int64_t reversed = (amt - refunded) / 2;
  w.s(id16(1 + (int64_t)(h4(t, r, 0) % (uint64_t)std::max<int64_t>(1, S->stores / 2))));
  w.s(id16(g_update * 10000000LL + (int64_t)(h4(t, r, 1) % 1000000) + 1));  // purchase id
  w.i(uni(t, r, 2, 1, 12));
  w.s(rk_item(t, r, 3));
  w.s(rk_cust(t, r, 4));
  w.s(date_str(upd_date(t, r, 5)));
  w.s(time_str((int64_t)(h4(t, r, 6) % 86400)));
  w.i(1 + (int64_t)(h4(t, r, 7) % (uint64_t)S->ss_tickets));
  w.i(qty);
  w.dec(amt);
  w.dec(amt * 5 / 100);
  w.dec(uni(t, r, 8, 50, 10000));
  w.dec(uni(t, r, 9, 100, 5000) * qty);
  w.dec(refunded);
  w.dec(reversed);
  w.dec(amt - refunded - reversed);
  w.s(id16(1 + (int64_t)(h4(t, r, 10) % (uint64_t)S->reasons)));
}

static void e_s_catalog_returns(Row& w, int64_t r) {
  const uint64_t t = T_S_CATALOG_RETURNS;
  int64_t qty = uni(t, r, 100, 1, 50);
  int64_t amt = uni(t, r, 101, 100, 20000) * qty;
  int64_t refunded = amt * (int64_t)(h4(t, r, 102) % 101) / 100;
  int64_t reversed = (amt - refunded) / 2;
  w.s(id16(1 + (int64_t)(h4(t, r, 0) % (uint64_t)std::max<int64_t>(1, S->call_centers / 2))));
  w.i(1 + (int64_t)(h4(t, r, 1) % (uint64_t)S->cs_orders));
  w.i(uni(t, r, 2, 1, 10));
  w.s(rk_item(t, r, 3));
  w.s(rk_cust(t, r, 4));
  w.s(rk_cust(t, r, 5));
  w.s(date_str(upd_date(t, r, 6)));
  w.s(time_str((int64_t)(h4(t, r, 7) % 86400)));
  w.i(qty);
  w.dec(amt);
  w.dec(amt * 5 / 100);
  w.dec(uni(t, r, 8, 50, 10000));
  w.dec(uni(t, r, 9, 100, 5000) * qty);
  w.dec(refunded);
  w.dec(reversed);
  w.dec(amt - refunded - reversed);
  w.s(id16(1 + (int64_t)(h4(t, r, 10) % (uint64_t)S->reasons)));
  w.s(id16(1 + (int64_t)(h4(t, r, 11) % 20)));
  w.s(id16(1 + (int64_t)(h4(t, r, 12) % (uint64_t)S->catalog_pages)));
  w.s(id16(1 + (int64_t)(h4(t, r, 13) % (uint64_t)S->warehouses)));
}

static void e_s_web_returns(Row& w, int64_t r) {
  const uint64_t t = T_S_WEB_RETURNS;
  int64_t qty = uni(t, r, 100, 1, 50);
  int64_t amt = uni(t, r, 101, 100, 20000) * qty;
  int64_t refunded = amt * (int64_t)(h4(t, r, 102) % 101) / 100;
  int64_t reversed = (amt - refunded) / 2;
  w.s(id16(1 + (int64_t)(h4(t, r, 0) % (uint64_t)std::max<int64_t>(1, S->web_pages / 2))));
  w.i(1 + (int64_t)(h4(t, r, 1) % (uint64_t)S->ws_orders));
  w.i(uni(t, r, 2, 1, 12));
  w.s(rk_item(t, r, 3));
  w.s(rk_cust(t, r, 4));
  w.s(rk_cust(t, r, 5));
  w.s(date_str(upd_date(t, r, 6)));
  w.s(time_str((int64_t)(h4(t, r, 7) % 86400)));
  w.i(qty);
  w.dec(amt);
  w.dec(amt * 5 / 100);
  w.dec(uni(t, r, 8, 50, 10000));
  w.dec(uni(t, r, 9, 100, 5000) * qty);
  w.dec(refunded);
  w.dec(reversed);
  w.dec(amt - refunded - reversed);
  w.s(id16(1 + (int64_t)(h4(t, r, 10) % (uint64_t)S->reasons)));
}

static void e_s_inventory(Row& w, int64_t r) {
  const uint64_t t = T_S_INVENTORY;
  int64_t items_tracked = std::max<int64_t>(100, S->items / 100);
  w.s(id16(r / items_tracked + 1));
  w.s(id16(1 + (int64_t)(h4(t, r, 1) % (uint64_t)std::max<int64_t>(1, S->items / 2))));
  w.s(date_str(upd_window_lo()));
  w.i(uni(t, r, 3, 0, 1000));
}

static void e_delete(Row& w, int64_t) {
  w.s(date_str(upd_window_lo()));
  w.s(date_str(upd_window_hi()));
}

// ---------------------------------------------------------------------------
// Driver: chunking, file naming, dispatch
// ---------------------------------------------------------------------------

typedef void (*EmitFn)(Row&, int64_t);

static EmitFn kEmitters[T_MAX] = {
  e_customer_address, e_customer_demographics, e_date_dim, e_warehouse, e_ship_mode,
  e_time_dim, e_reason, e_income_band, e_item, e_store, e_call_center, e_customer,
  e_web_site, e_store_returns, e_household_demographics, e_web_page, e_promotion,
  e_catalog_page, e_inventory, e_catalog_returns, e_web_returns, e_web_sales,
  e_catalog_sales, e_store_sales,
  e_s_purchase, e_s_purchase_lineitem, e_s_catalog_order, e_s_catalog_order_lineitem,
  e_s_web_order, e_s_web_order_lineitem, e_s_store_returns, e_s_catalog_returns,
  e_s_web_returns, e_s_inventory, e_delete, e_delete,
};

// tables too small to split across children (single chunk, child 1 only)
static bool is_small(int tid, int64_t rows) {
  if (tid == T_DELETE || tid == T_INVENTORY_DELETE) return true;
  return rows < 50000;
}

static int gen_table(int tid, const std::string& dir, int parallel, int child) {
  int64_t rows = S->rows[tid];
  int64_t lo = 0, hi = rows;
  if (is_small(tid, rows)) {
    if (child != 1) return 0;  // dsdgen: small tables only in chunk 1
  } else {
    lo = rows * (child - 1) / parallel;
    hi = rows * child / parallel;
  }
  char path[4096];
  if (parallel > 1)
    snprintf(path, sizeof path, "%s/%s_%d_%d.dat", dir.c_str(), kTableNames[tid], child, parallel);
  else
    snprintf(path, sizeof path, "%s/%s.dat", dir.c_str(), kTableNames[tid]);
  // -update file naming carries the update number like dsdgen's delete_<n>
  if (g_update > 0 && (tid == T_DELETE || tid == T_INVENTORY_DELETE))
    snprintf(path, sizeof path, "%s/%s_%d.dat", dir.c_str(), kTableNames[tid], g_update);
  FILE* f = fopen(path, "w");
  if (!f) { fprintf(stderr, "ndsgen: cannot open %s\n", path); return 1; }
  std::vector<char> buf(1 << 20);
  setvbuf(f, buf.data(), _IOFBF, buf.size());
  Row w(f);
  for (int64_t r = lo; r < hi; r++) {
    kEmitters[tid](w, r);
    w.end();
  }
  fclose(f);
  return 0;
}

int main(int argc, char** argv) {
  double scale = 1.0;
  int parallel = 1, child = 1;
  std::string dir = ".", only_table;
  uint64_t seed = 19620718ULL;
  int update = 0;
  for (int i = 1; i < argc; i++) {
    std::string a = argv[i];
    auto next = [&]() -> const char* {
      if (i + 1 >= argc) { fprintf(stderr, "ndsgen: missing value for %s\n", a.c_str()); exit(2); }
      return argv[++i];
    };
    if (a == "-scale") scale = atof(next());
    else if (a == "-parallel") parallel = atoi(next());
    else if (a == "-child") child = atoi(next());
    else if (a == "-dir") dir = next();
    else if (a == "-table") only_table = next();
    else if (a == "-update") update = atoi(next());
    else if (a == "-rngseed") seed = (uint64_t)atoll(next());
    else if (a == "-help" || a == "--help") {
      printf("usage: ndsgen -scale SF -dir DIR [-parallel N -child C] [-table T] "
             "[-update U] [-rngseed S]\n");
      return 0;
    } else {
      fprintf(stderr, "ndsgen: unknown flag %s\n", a.c_str());
      return 2;
    }
  }
  if (scale <= 0 || parallel < 1 || child < 1 || child > parallel) {
    fprintf(stderr, "ndsgen: invalid -scale/-parallel/-child\n");
    return 2;
  }
  Scaling scaling(scale);
  S = &scaling;
  g_update = update;
  // refresh data varies per update number; delete windows derive from the
  // update number itself, so they stay deterministic
  g_seed = update > 0 ? splitmix64(seed ^ (uint64_t)update * 0xC2B2AE3D27D4EB4FULL) : seed;

  int first = update > 0 ? T_S_PURCHASE : 0;
  int last = update > 0 ? T_MAX : T_S_PURCHASE;
  int status = 0;
  for (int tid = first; tid < last; tid++) {
    if (!only_table.empty() && only_table != kTableNames[tid]) continue;
    status |= gen_table(tid, dir, parallel, child);
  }
  return status;
}
